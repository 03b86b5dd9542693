"""Seconds of set-up spent in the mapper's own Python: building the
schedule, placing the KV pool and compiling the schedule into a program
object, each less the JAX compile time inside it, summed over the
program's compile record (``repro.obs.compiles()``). Nothing to read
where the program keeps no such record."""

from repro import obs


def read(ctx):
    record = getattr(obs, "compiles", None)
    if record is None:
        return None
    return sum(b.mapper_s for b in record())
