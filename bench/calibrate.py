"""Readings from which a cell's correctness limits are set.

    python3 bench/calibrate.py --workload NAME --seeds 1,2,3 [--seconds S]

For each seed, in one process: the program's own reading of every number
the cell compares, and the control's. A serving cell runs a short window
at the cell's own load and scores the same finished requests twice: the
served tokens against the reference (the program's reading), and the
token that the reference computed in float8 e4m3 puts first at each of
those positions (the control's). A training cell runs its three checked
steps; its control is the reference at ``high`` precision (three bf16
passes) in the program's place, and its planted fault the reference
trained on half of each batch, the mean taken over the rest. Each seed
prints one JSON line; the benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))

import harness  # noqa: E402


def serve_seed(cell, seed: int, seconds: float, counter) -> dict:
    drv = harness.load_module("drivers", "serve")
    r = drv.Run(cell, seed, seconds)
    r.setup()
    if cell.traffic["kind"] == "open_loop":
        r.open_loop()
    else:
        r.closed_loop()
    sample = r.sample()
    r.free()
    w = r.ref.make_weights(cell.config, seed)
    prog = [r.ref.served_gaps(cell.config, w, q.prompt, q.out)
            for q in sample]
    ctrl = [r.ref.served_gaps(cell.config, w, q.prompt, q.out, control=True)
            for q in sample]
    out = {"seed": seed, "requests": len(sample),
           "served_tokens": int(sum(len(g) for g in prog))}
    for tag, gaps in (("program", prog), ("control", ctrl)):
        out.update({f"{tag}_{k}": v
                    for k, v in drv.gap_numbers(gaps).items()})
    return out


def train_seed(cell, seed: int, counter) -> dict:
    import jax
    import numpy as np
    drv = harness.load_module("drivers", "train")
    with jax.default_matmul_precision(cell.config["matmul_precision"]):
        r = drv.Run(cell, seed, 0.0, harness.Clock(), counter)
        r.go(None)
        out = {"seed": seed}
        for name, c in zip(("loss_rel_gap", "grad_norm_rel_gap",
                            "update_norm_rel_gap"), r.check()):
            out[f"program_{name}"] = c.value
    ref = r.ref
    p0 = jax.tree.map(np.asarray, r.p0)
    want = ref.run(cell.config, p0, r.batches, precision="highest")
    half = [(x[: len(x) // 2], y[: len(y) // 2]) for x, y in r.batches]
    for tag, got in (("control", ref.run(cell.config, p0, r.batches,
                                         precision="high")),
                     ("half_batch", ref.run(cell.config, p0, half,
                                            precision="highest"))):
        out.update(_train_gaps(tag, cell, got, want, p0))
    return out


def _train_gaps(tag, cell, got, want, p0) -> dict:
    import jax
    import numpy as np
    drv = harness.load_module("drivers", "train")
    d_got = jax.tree.map(lambda a, b: np.asarray(a) - b, got["params"], p0)
    d_want = jax.tree.map(lambda a, b: np.asarray(a) - b, want["params"], p0)
    loss = max(abs(a - b) / abs(b) for a, b in zip(got["losses"],
                                                   want["losses"]))
    return {f"{tag}_loss_rel_gap": loss,
            f"{tag}_grad_norm_rel_gap": drv.norm_gap(got["grad"], want["grad"],
                                                     want["grad"]),
            f"{tag}_update_norm_rel_gap": drv.norm_gap(d_got, d_want,
                                                       want["grad"])}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    harness.use_compile_cache()
    try:
        harness.require_chips(cell.chips)
    except harness.NoDevice as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 3
    counter = harness.CompileCounter()
    for seed in (int(s) for s in args.seeds.split(",")):
        if cell.config["driver"] == "serve":
            line = serve_seed(cell, seed, args.seconds, counter)
        else:
            line = train_seed(cell, seed, counter)
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
