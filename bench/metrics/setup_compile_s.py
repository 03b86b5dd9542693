"""Seconds of set-up spent in the backend compiler or reading compiled
programs back from the persistent compilation cache: the sum of the
backend-compile seconds (the cache read included) over the program's
compile record (``repro.obs.compiles()``). Nothing to read where the
program keeps no such record."""

from repro import obs


def read(ctx):
    record = getattr(obs, "compiles", None)
    if record is None:
        return None
    return sum(b.compile_s + b.retrieval_s for b in record())
