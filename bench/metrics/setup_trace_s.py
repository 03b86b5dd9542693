"""Seconds of set-up spent tracing the program's jitted programs to
jaxprs and lowering them to MLIR: the sum of the trace and lowering
seconds over the program's compile record (``repro.obs.compiles()``),
which the traced run builds in the same set-up as the untraced one.
Nothing to read where the program keeps no such record."""

from repro import obs


def read(ctx):
    record = getattr(obs, "compiles", None)
    if record is None:
        return None
    return sum(b.trace_s + b.lower_s for b in record())
