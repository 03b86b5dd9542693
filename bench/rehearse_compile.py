"""Compile a serving cell's programs for a described TPU v5e, without a chip.

    JAX_PLATFORMS=cpu python3 bench/rehearse_compile.py --workload NAME

Builds the cell's engine on the CPU with abstract weights, then compiles
its pim decode program and its largest prefill bucket for one chip of a
described ``v5e:2x2`` topology, and prints each program's memory analysis
beside the device's 16 GB. Nothing runs: this shows only whether the
chip's compiler accepts the programs and whether they fit.
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))

import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    # the kernels pick compiled mode from the backend: steer them to the
    # TPU lowering while the host stays the CPU
    jax.default_backend = lambda: "tpu"
    ad = harness.load_module("adapters", cell.config["family"])
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    eng, shapes = ad.abstract_engine(cell.config, cell.traffic)

    def spec(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one)

    out = {}
    b = cell.traffic["slots"]
    probe = (jax.tree.map(spec, shapes), jax.tree.map(spec, eng.cache),
             spec(jnp.zeros(b, jnp.int32)), spec(eng.kv.device_table()),
             spec(jnp.zeros(b, jnp.int32)))
    comp = eng.pim_program.jitted.lower(*probe).compile()
    out["decode"] = _mem(comp)
    t_pad = max(harness.prefill_buckets(
        cell.traffic, harness.load_spec()["run_seconds"]))
    pre = eng._prefill_fn.lower(
        jax.tree.map(spec, shapes), jax.tree.map(spec, eng.cache),
        spec(jnp.zeros(t_pad, jnp.int32)), spec(eng.kv.device_table()[0]),
        spec(jnp.int32(0)), spec(jnp.int32(1))).compile()
    out[f"prefill_{t_pad}"] = _mem(pre)
    pool = sum(x.nbytes for x in jax.tree.leaves(eng.cache))
    weights = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(shapes))
    worst = max(v["temp"] + v["output"] for v in out.values())
    out["pool_bytes"] = pool
    out["weight_bytes"] = weights
    out["peak_estimate_bytes"] = weights + pool + worst
    print(json.dumps(out, indent=1))
    return 0


def _mem(compiled) -> dict:
    ma = compiled.memory_analysis()
    return {"argument": ma.argument_size_in_bytes,
            "output": ma.output_size_in_bytes,
            "alias": ma.alias_size_in_bytes,
            "temp": ma.temp_size_in_bytes}


if __name__ == "__main__":
    sys.exit(main())
