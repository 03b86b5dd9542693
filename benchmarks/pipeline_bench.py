"""Microbatch pipeline vs sequential schedule execution (beyond-paper).

Times the partitioned pipeline plan three ways:

  * **modeled** — ``Schedule.pipeline(M, K)`` steady-state timeline on the
    paper's LeNet-5 train step (4 partitions) and the llama3-8b decode
    step, with and without **scan expansion**. The historical full-llama
    cut at 2 partitions is recorded unbarred (the scanned layer stack is
    one uncuttable unit there, so its speedup is structural ~1x); the
    expanded llama3-8b smoke decode (``expand_scans=True`` hoists the
    stack into resident per-layer copies) carries a >= 2.0x bar at
    4 partitions — the headline of the scan-residency feature. LeNet
    keeps its >= 1.5x bar.
  * **executed** — wall-clock steps/s of the real GPipe microbatch driver
    (``repro.parallel.pipeline.run_partitioned``) vs the sequential
    partitioned program on LeNet forward (no bar: driver overhead only).
  * **measured async** — wall-clock of the device-backed async driver
    (``run_partitioned_async`` over stages pinned to 4 devices) vs
    sequential chaining of the same unpinned stage programs, at 8
    microbatches on the expanded llama3-8b smoke decode, in this process
    on its own devices (on a CPU, force 4 host devices with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=4``; with fewer
    than 4 devices the entry is recorded as not measured).
    Bit-exact parity with the sequential driver is gated always; the
    two wall-clock gates — a >= 1.3x speedup bar and a non-blocking
    dispatch proof (the async driver must *return* well before the work
    completes) — apply on hosts with >= 2 CPU cores, where overlap is
    physically possible (CI runners). On a 1-core host both numbers are
    still recorded, honestly, as whatever the serialized queues deliver.

Emits CSV rows and writes ``BENCH_pipeline.json`` next to the repo root
so the perf trajectory is recorded run over run.
"""

from __future__ import annotations

import json
import os
import pathlib
import time

import jax
import jax.numpy as jnp

MICROBATCHES = 8
SPEEDUP_BAR = 1.5
EXPANDED_SPEEDUP_BAR = 2.0          # modeled, llama3-8b smoke, 4 partitions
ASYNC_SPEEDUP_BAR = 1.3             # measured, >= 2 cores only
ASYNC_DISPATCH_FRACTION_MAX = 0.5   # async driver must return well early

_OUT = pathlib.Path(__file__).resolve().parent.parent / "BENCH_pipeline.json"


def _timeline_entry(sched, microbatches: int, partitions: int) -> dict:
    tl = sched.pipeline(microbatches, partitions=partitions)
    return {
        "partitions": tl.n_partitions,
        "microbatches": tl.microbatches,
        "interval_s": tl.interval_s,
        "fill_s": tl.fill_s,
        "makespan_s": tl.makespan_s,
        "sequential_s": tl.sequential_s,
        "speedup": tl.speedup,
        "steady_sets_per_s": tl.steady_sets_per_s,
        "bottleneck": tl.bottleneck,
    }


def _executed_entry(microbatches: int) -> dict:
    from repro import mapper
    from repro.models import lenet
    from repro.parallel import pipeline as pipe_mod
    from repro.configs.lenet5 import CONFIG

    params = lenet.init_lenet(jax.random.PRNGKey(0), CONFIG)
    mb_imgs = [jax.random.normal(jax.random.PRNGKey(m), (4, 28, 28, 1),
                                 jnp.float32) for m in range(microbatches)]
    prog = mapper.compile_lenet("serve", batch=4, partitions=2)
    flat_per_mb = [prog.flatten_args(params, im) for im in mb_imgs]

    def gpipe_all():
        return pipe_mod.run_partitioned(prog.stages, prog.out_refs,
                                        flat_per_mb)

    def sequential_all():
        return [prog(params, im) for im in mb_imgs]

    jax.block_until_ready(jax.tree.leaves(gpipe_all()))     # warm stage jits
    jax.block_until_ready(jax.tree.leaves(sequential_all()))
    t0 = time.perf_counter()
    for _ in range(3):
        jax.block_until_ready(jax.tree.leaves(gpipe_all()))
    t_pipe = (time.perf_counter() - t0) / 3
    t0 = time.perf_counter()
    for _ in range(3):
        jax.block_until_ready(jax.tree.leaves(sequential_all()))
    t_seq = (time.perf_counter() - t0) / 3
    return {
        "microbatches": microbatches,
        "gpipe_steps_per_s": 1.0 / t_pipe,
        "sequential_steps_per_s": 1.0 / t_seq,
        "driver_overhead": t_pipe / t_seq,
    }


def _async_measured_entry() -> dict:
    """Async device-pinned driver vs sequential chaining of the same
    unpinned stage programs, on this process's own ``jax.devices()``: a
    child process could not reach a device this one already holds. With
    fewer than 4 devices the entry is recorded as not measured."""
    from repro import mapper
    from repro.parallel import pipeline as pipe_mod

    devs = jax.devices()
    if len(devs) < 4:
        return {"not_measured": f"needs 4 devices, found {len(devs)}",
                "platform": devs[0].platform}
    sched = mapper.map_arch("llama3-8b", "serve", smoke=True, partitions=4,
                            expand_scans=True)
    plain = mapper.compile_partitioned(sched, use_cache=False)
    pinned = mapper.compile_partitioned(sched, use_cache=False,
                                        devices=devs[:4])

    # concrete per-microbatch inputs straight from the traced avals
    def mk(aval, seed):
        if jnp.issubdtype(aval.dtype, jnp.floating):
            return jax.random.normal(jax.random.PRNGKey(seed), aval.shape,
                                     aval.dtype)
        return jnp.zeros(aval.shape, aval.dtype)

    avals = [v.aval for v in sched.graph.closed_jaxpr.jaxpr.invars]
    mbs = [[mk(a, 1000 * m + i) for i, a in enumerate(avals)]
           for m in range(MICROBATCHES)]

    def seq():
        return pipe_mod.run_partitioned(plain.stages, plain.out_refs, mbs)

    def asy():
        return pipe_mod.run_partitioned_async(pinned.stages,
                                              pinned.out_refs, mbs)

    o_seq = seq()                       # warm stage jits (both rings)
    o_asy = asy()
    parity = 0.0
    for r1, r2 in zip(o_seq, o_asy):
        for a, b in zip(r1, r2):
            parity = max(parity, float(jnp.max(jnp.abs(
                jnp.asarray(a, jnp.float32) - jnp.asarray(b, jnp.float32)))))

    def best(fn, reps=5):
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(jax.tree.leaves(fn()))
            ts.append(time.perf_counter() - t0)
        return min(ts)

    t_seq = best(seq)
    t_asy = best(asy)
    # non-blocking dispatch proof: the async driver returns while the
    # device queues still hold work
    t0 = time.perf_counter()
    out = asy()
    t_dispatch = time.perf_counter() - t0
    jax.block_until_ready(jax.tree.leaves(out))
    t_total = time.perf_counter() - t0
    return {
        "microbatches": MICROBATCHES,
        "platform": devs[0].platform,
        "devices": 4,
        "cpu_count": os.cpu_count() or 1,
        "t_sequential_s": t_seq,
        "t_async_s": t_asy,
        "speedup": t_seq / t_asy,
        "dispatch_s": t_dispatch,
        "dispatch_fraction": t_dispatch / t_total,
        "parity_max_dev": parity,
        "speedup_bar": ASYNC_SPEEDUP_BAR,
        "speedup_bar_applies": (os.cpu_count() or 1) >= 2,
    }


def run() -> list[str]:
    from repro import mapper

    results: dict[str, dict] = {}

    # modeled: balanced 4-partition lenet5 train step (carries the bar)
    sched = mapper.map_lenet("train", batch=8)
    results["lenet5_train_modeled"] = _timeline_entry(
        sched, MICROBATCHES, partitions=4)

    # modeled: full llama3-8b decode at the historical 2-partition cut
    # (unbarred — without expansion the scanned stack is one uncuttable
    # partition; kept as the before-picture of the expanded entry below)
    batch = 1
    sched = mapper.map_arch("llama3-8b", "serve", seq_len=32, batch=batch,
                            partitions=2)
    entry = _timeline_entry(sched, MICROBATCHES, partitions=2)
    entry["steady_tokens_per_s"] = batch * entry["steady_sets_per_s"]
    results["llama3_8b_decode_modeled"] = entry

    # modeled: llama3-8b smoke decode with the stack expanded into
    # resident per-layer copies — partition cuts land inside it (barred)
    sched = mapper.map_arch("llama3-8b", "serve", smoke=True,
                            expand_scans=True)
    entry = _timeline_entry(sched, MICROBATCHES, partitions=4)
    entry["expand_scans"] = True
    entry["steady_tokens_per_s"] = entry["steady_sets_per_s"]
    results["llama3_8b_smoke_expanded_modeled"] = entry

    # executed: real GPipe driver over the partition programs
    results["lenet5_forward_executed"] = _executed_entry(MICROBATCHES)

    # measured: async device-backed driver vs sequential chaining
    results["llama3_8b_async_measured"] = _async_measured_entry()

    _OUT.write_text(json.dumps(results, indent=2, sort_keys=True) + "\n")

    # the acceptance bars are real gates: benchmarks.run exits non-zero
    # on a raise, so a regression below a bar fails CI
    lt = results["lenet5_train_modeled"]
    assert lt["speedup"] >= SPEEDUP_BAR, (
        f"lenet5 train: pipelined speedup {lt['speedup']:.2f} at "
        f"{MICROBATCHES} microbatches fell below the "
        f"{SPEEDUP_BAR}x acceptance bar")

    ex = results["llama3_8b_smoke_expanded_modeled"]
    assert ex["speedup"] >= EXPANDED_SPEEDUP_BAR, (
        f"llama3-8b smoke expanded: modeled speedup {ex['speedup']:.2f} "
        f"at 4 partitions fell below the {EXPANDED_SPEEDUP_BAR}x bar — "
        f"scan expansion stopped cutting the stack")

    am = results["llama3_8b_async_measured"]
    assert "not_measured" in am or am["parity_max_dev"] == 0.0, (
        f"async driver diverged from sequential chaining by "
        f"{am['parity_max_dev']:.3e}")
    if am.get("speedup_bar_applies"):
        # both wall-clock gates need >= 2 cores: on one core the XLA
        # compute threads and the Python dispatch loop share the core,
        # so neither overlap nor early-return is physically observable
        # (the numbers are still recorded above, honestly serialized)
        assert am["dispatch_fraction"] <= ASYNC_DISPATCH_FRACTION_MAX, (
            f"async driver blocked during dispatch: returned after "
            f"{am['dispatch_fraction']:.0%} of the wall time")
        assert am["speedup"] >= ASYNC_SPEEDUP_BAR, (
            f"async device-backed driver: measured speedup "
            f"{am['speedup']:.2f} on {am['cpu_count']} cores fell below "
            f"the {ASYNC_SPEEDUP_BAR}x bar")

    rows = []
    for tag, r in results.items():
        for key in ("speedup", "steady_sets_per_s", "steady_tokens_per_s",
                    "interval_s", "gpipe_steps_per_s", "driver_overhead",
                    "dispatch_fraction", "parity_max_dev"):
            if key in r:
                note = ""
                if (tag, key) == ("lenet5_train_modeled", "speedup"):
                    note = f"target>={SPEEDUP_BAR}"
                elif (tag, key) == ("llama3_8b_smoke_expanded_modeled",
                                    "speedup"):
                    note = f"target>={EXPANDED_SPEEDUP_BAR}"
                elif (tag, key) == ("llama3_8b_async_measured", "speedup"):
                    note = (f"target>={ASYNC_SPEEDUP_BAR}"
                            if r.get("speedup_bar_applies")
                            else f"1-core host: {ASYNC_SPEEDUP_BAR}x bar "
                                 f"applies on >=2 cores")
                rows.append(f"pipeline.{tag}.{key},{r[key]:.4g},{note}")
    rows.append(f"pipeline.json,{_OUT.name},perf trajectory artifact")
    return rows


if __name__ == "__main__":
    print("\n".join(run()))
