"""A whole benchmark run on the CPU, for the rehearsal tests.

    JAX_PLATFORMS=cpu python3 bench/tests/cpu_run.py --workload NAME \
        --seed N --seconds S --trace 0|1

The same as ``bench/run.py`` except that it skips the look for a chip:
it drives the cell on JAX's CPU device with the Pallas kernels
interpreted, so that the traffic, the loops, the checks and the metric
arithmetic run here at smoke sizes. Its per-layer numbers use the TPU
peaks table only so that the arithmetic runs; they are no device
measurement and are never reported as one.
"""

from __future__ import annotations

import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import harness  # noqa: E402
import run as run_mod  # noqa: E402
import work  # noqa: E402


def main(argv=None) -> int:
    import jax
    clock = harness.Clock()
    args = run_mod.parse(argv)
    cell = harness.load_cell(args.workload)
    devs = jax.devices()[:cell.chips]
    table = work.peaks("TPU v5 lite")
    work.peaks = lambda kind: table
    counter = harness.CompileCounter()
    driver = harness.load_module("drivers", cell.config["driver"])
    driver.run(cell, args, clock, counter, devs)
    return 0


if __name__ == "__main__":
    sys.exit(main())
