"""Run one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload NAME --seed N --seconds S --trace 0|1

Reads ``BENCHMARK.json`` at the root of the checkout, finds the cell and
the files it names, sets up the program, measures for ``--seconds`` and
checks the timed path's output against the plain reference. The last line
of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics), ``device`` and, traced, a
``breakdown``; the numbers compared for ``correct`` come last, and again
as the last lines of standard error. Exits non-zero, printing no result,
unless JAX's first device is a TPU and there are as many as the cell asks
for.
"""

from __future__ import annotations

import argparse
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))

import harness  # noqa: E402


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    clock = harness.Clock()
    args = parse(argv)
    if args.seed < 0:
        print("run: --seed must be a whole number >= 0", file=sys.stderr)
        return 2
    cell = harness.load_cell(args.workload)
    cache = harness.use_compile_cache()
    try:
        devs = harness.require_chips(cell.chips)
    except harness.NoDevice as e:
        print(f"run: {e}", file=sys.stderr)
        return 3
    counter = harness.CompileCounter()
    harness.log(workload=cell.name, seed=args.seed, seconds=args.seconds,
                trace=args.trace, compile_cache=cache,
                device_kind=devs[0].device_kind)
    driver = harness.load_module("drivers", cell.config["driver"])
    ok = driver.run(cell, args, clock, counter, devs)
    return 0 if ok is not None else 1


if __name__ == "__main__":
    sys.exit(main())
