"""GPipe-style pipeline parallelism: shard_map stages and PIM partitions.

Two pipelining substrates share this module's GPipe schedule (classic
fill-drain over M microbatches and P stages — T = M + P - 1 ticks; at
tick t, stage s processes microbatch (t - s) when 0 <= t - s < M; bubble
fraction = (P-1)/(M+P-1)):

  * **device pipelining** (``pipeline_forward`` / ``make_pipelined_fn``):
    the pod axis carries pipeline stages; each device holds a contiguous
    slice of the layer stack and microbatches stream through
    ``collective_permute`` handoffs, as an explicit shard_map program
    (GSPMD cannot derive pipelining automatically). The layer stack must
    be stacked per-stage: params leaves shaped [P, layers_per_stage, ...]
    with the leading P dim sharded over the pipe axis.
  * **PIM partition pipelining** (``gpipe_grid`` / ``run_partitioned`` /
    ``run_partitioned_async`` / ``gpipe_value_and_grad``): the stages
    are the per-partition programs of
    ``repro.mapper.compile.compile_partitioned`` — weight blocks stay
    resident on their tiles and activation sets stream through the
    explicit transfer points. When ``compile_partitioned(...,
    devices=...)`` pinned each stage to its own JAX device, the drivers
    commit every cell's inputs there with non-blocking ``device_put``
    and ``run_partitioned_async`` keeps the whole grid on the devices'
    async queues, so fill/steady/drain overlap is measured wall-clock
    speedup, not just the modeled timeline. The forward driver walks the GPipe grid;
    training differentiates *per stage* with ``jax.vjp`` (forward ticks
    stash pullbacks, backward ticks run them in reverse grid order,
    accumulating boundary cotangents stage-to-stage and argument
    cotangents across microbatches) — real GPipe, not grad-of-a-replay.
    Microbatch means over equal slices reproduce full-batch mean losses
    and gradients to fp32 tolerance, which is what lets
    ``Trainer(backend="pim", microbatches=M, partitions=K)`` match the
    jit backend.

Correctness: tests/test_pipeline.py checks a 2-stage x 4-microbatch
shard_map run against the unpipelined reference on a forced 8-device
host mesh; tests/test_partition.py checks the PIM partition drivers
against ``jax.jit`` of the unpartitioned step.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro import obs


def pipeline_forward(x, stage_params, stage_fn: Callable, *, axis: str,
                     n_stages: int, n_micro: int):
    """Run inside shard_map. x: [n_micro, mb, ...] (replicated along the
    pipe axis); stage_params: this device's stage slice. Returns the final
    stage's outputs [n_micro, mb, ...] (valid on the last stage, broadcast
    back by the caller's out_spec choice).

    stage_fn(stage_params, x_mb) -> y_mb applies this stage's layers.
    """
    stage = jax.lax.axis_index(axis)
    # shard_map hands each device its [1, ...] slice of the stacked stage
    # params — drop the leading stage dim
    stage_params = jax.tree.map(lambda a: a[0], stage_params)
    mb_shape = x.shape[1:]
    n_ticks = n_micro + n_stages - 1

    def tick(carry, t):
        inflight, outputs = carry
        # stage 0 injects microbatch t; others take the permuted activation
        mb_idx = jnp.clip(t, 0, n_micro - 1)
        injected = x[mb_idx]
        cur_in = jnp.where(stage == 0, injected, inflight)
        active = (t - stage >= 0) & (t - stage < n_micro)
        out = stage_fn(stage_params, cur_in)
        out = jnp.where(active, out, jnp.zeros_like(out))
        # pass activations downstream (stage s -> s+1)
        perm = [(i, i + 1) for i in range(n_stages - 1)]
        nxt = jax.lax.ppermute(out, axis, perm)
        # last stage records its finished microbatch
        done_idx = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
        is_done = (stage == n_stages - 1) & (t - stage >= 0) & (
            t - stage < n_micro)
        upd = jax.lax.dynamic_update_index_in_dim(outputs, out, done_idx, 0)
        outputs = jnp.where(is_done, upd, outputs)
        return (nxt, outputs), None

    # mark the carries as device-varying along the pipe axis (shard_map
    # vma typing: they hold per-stage values)
    inflight0 = jax.lax.pcast(jnp.zeros(mb_shape, x.dtype), (axis,),
                              to="varying")
    outputs0 = jax.lax.pcast(jnp.zeros((n_micro,) + mb_shape, x.dtype),
                             (axis,), to="varying")
    (_, outputs), _ = jax.lax.scan(tick, (inflight0, outputs0),
                                   jnp.arange(n_ticks))
    # broadcast final outputs from the last stage to all stages so the
    # shard_map out_spec can be replicated along the pipe axis (psum of the
    # masked value = broadcast; ppermute can't fan out one source)
    is_last = (stage == n_stages - 1).astype(outputs.dtype)
    outputs = jax.lax.psum(outputs * is_last, axis)
    return outputs


# ---------------------------------------------------------------------------
# GPipe drivers over PIM partition stage programs
# ---------------------------------------------------------------------------


def gpipe_grid(n_stages: int, n_micro: int):
    """Yield ``(tick, stage, microbatch)`` in GPipe fill-drain order."""
    for t in range(n_micro + n_stages - 1):
        for s in range(n_stages):
            m = t - s
            if 0 <= m < n_micro:
                yield t, s, m


def _resolve(ref, flat_args, stage_outs):
    if ref[0] == "arg":
        return flat_args[ref[1]]
    if ref[0] == "stage":
        return stage_outs[ref[1]][ref[2]]
    return ref[1]                              # ("lit", val)


def tick_phase(t: int, n_stages: int, n_micro: int) -> str:
    """GPipe phase of tick ``t``: 'fill' while the first microbatch has
    not reached the last stage, 'drain' once the last microbatch has been
    injected, 'steady' between (fill wins the n_micro < n_stages overlap)."""
    if t < n_stages - 1:
        return "fill"
    if t >= n_micro:
        return "drain"
    return "steady"


def _traceable(vals) -> bool:
    """True when the cell runs eagerly (no jit tracers among operands) —
    span durations are only meaningful for real work, never trace time."""
    return not any(isinstance(x, jax.core.Tracer) for x in vals)


def _stage_put(stage, ins, *, tick=None, micro=None):
    """Commit a stage's inputs onto its pinned device, if it has one.

    ``jax.device_put`` is non-blocking: it enqueues the transfer and
    returns immediately, even when the source value is itself still being
    computed on another device's queue. Because the stage's jitted
    program then follows its committed inputs, this is the entire
    device-routing mechanism — no ``jit(device=...)``. Transfers at cut
    points are recorded as zero-duration tracer instants (never blocked
    on) so traces show *when* activations were handed off without
    serializing the pipeline."""
    dev = getattr(stage, "device", None)
    if dev is None:
        return ins
    moved = [jax.device_put(x, dev) for x in ins]
    tr = obs.tracer()
    if tr.enabled and _traceable(ins):
        tr.instant("transfer", lane="pipeline", device=str(dev),
                   tick=tick, micro=micro)
    return moved


def run_partitioned(stages: Sequence, out_refs: Sequence,
                    flat_args_per_mb: Sequence[Sequence]) -> list[list]:
    """Stream M microbatches through the partition stage programs in GPipe
    fill-drain order; returns each microbatch's flat outputs.

    ``stages`` are ``StageProgram``-shaped objects (``fn``, ``in_refs``);
    ``flat_args_per_mb[m]`` is microbatch m's flat argument list (from
    ``PartitionedProgram.flatten_args``). Microbatches are independent
    activation sets, so the interleaving cannot change numerics — each
    output equals the stages composed sequentially on that microbatch.
    """
    n_micro = len(flat_args_per_mb)
    n_stages = len(stages)
    outs = [[None] * n_stages for _ in range(n_micro)]
    for t, s, m in gpipe_grid(n_stages, n_micro):
        ins = [_resolve(r, flat_args_per_mb[m], outs[m])
               for r in stages[s].in_refs]
        ins = _stage_put(stages[s], ins, tick=t, micro=m)
        run = getattr(stages[s], "jitted", None) or stages[s].fn
        tr = obs.tracer()
        if tr.enabled and _traceable(ins):
            with tr.span(f"{tick_phase(t, n_stages, n_micro)}:tick",
                         lane="pipeline", tick=t, stage=s, micro=m):
                outs[m][s] = run(*ins)
                jax.block_until_ready(outs[m][s])
        else:
            outs[m][s] = run(*ins)
    return [[_resolve(r, flat_args_per_mb[m], outs[m]) for r in out_refs]
            for m in range(n_micro)]


def run_partitioned_async(stages: Sequence, out_refs: Sequence,
                          flat_args_per_mb: Sequence[Sequence]) -> list[list]:
    """Async GPipe driver over device-pinned stage programs.

    Same grid, same dataflow, same numerics as :func:`run_partitioned` —
    the difference is purely *when* Python waits. Every cell's inputs are
    committed to the stage's pinned device with non-blocking
    ``device_put`` and the stage's jitted program is dispatched onto that
    device's async queue; the Python loop never blocks, so by the time
    the grid is enumerated, every device holds its whole per-stage work
    queue and fill/steady/drain overlap happens in wall-clock time (XLA
    executes each queue in order; cross-device transfers synchronize at
    the cut points). Callers observe the overlap simply by blocking on
    the returned outputs.

    With a tracer enabled the driver records per-stage lanes
    (``pipeline:stage{s}``) with ``block_until_ready`` inside each span
    plus transfer instants at the cut points — faithful per-cell
    occupancy, but the measurement itself serializes the queues, so
    enable tracing to *attribute* time and disable it to *measure*
    speedup.

    Stages without a pinned device still work (single shared queue);
    they just cannot overlap with each other.
    """
    n_micro = len(flat_args_per_mb)
    n_stages = len(stages)
    outs = [[None] * n_stages for _ in range(n_micro)]
    tr = obs.tracer()
    # per-call transfer memo: the same source array (params reused by
    # every microbatch) is copied to a given stage device once, not once
    # per cell — arrays are immutable, so reuse is always safe
    moved: dict[tuple[int, str], Any] = {}

    def put(x, dev, t, m):
        key = (id(x), str(dev))
        hit = moved.get(key)
        if hit is not None:
            return hit
        y = jax.device_put(x, dev)
        moved[key] = y
        if tr.enabled and _traceable((x,)):
            tr.instant("transfer", lane="pipeline", device=str(dev),
                       tick=t, micro=m)
        return y

    for t, s, m in gpipe_grid(n_stages, n_micro):
        ins = [_resolve(r, flat_args_per_mb[m], outs[m])
               for r in stages[s].in_refs]
        dev = getattr(stages[s], "device", None)
        if dev is not None:
            ins = [put(x, dev, t, m) for x in ins]
        run = getattr(stages[s], "jitted", None) or stages[s].fn
        if tr.enabled and _traceable(ins):
            with tr.span(f"{tick_phase(t, n_stages, n_micro)}:tick",
                         lane=f"pipeline:stage{s}", tick=t, stage=s,
                         micro=m):
                outs[m][s] = run(*ins)
                jax.block_until_ready(outs[m][s])
        else:
            outs[m][s] = run(*ins)
    return [[_resolve(r, flat_args_per_mb[m], outs[m]) for r in out_refs]
            for m in range(n_micro)]


def _zero_cot(x):
    """A zero cotangent for one primal output (float0 for int/bool)."""
    if jnp.issubdtype(jnp.result_type(x), jnp.inexact):
        return jnp.zeros_like(x)
    return np.zeros(jnp.shape(x), dtype=jax.dtypes.float0)


def _acc(a, b):
    if b is None or (hasattr(b, "dtype") and b.dtype == jax.dtypes.float0):
        return a
    return b if a is None else a + b


def gpipe_value_and_grad(stages: Sequence, loss_ref: tuple,
                         flat_args_per_mb: Sequence[Sequence],
                         grad_argnums: Sequence[int]):
    """GPipe forward/backward over partition stage programs.

    Forward ticks run ``jax.vjp`` per (microbatch, stage) and stash the
    pullbacks; backward ticks walk the grid in reverse, feeding each
    stage's output cotangents (seeded with 1/M at the loss, accumulated
    from downstream consumers elsewhere) through its pullback and
    scattering the input cotangents to producer stages and to the global
    argument gradient accumulators.

    Returns ``(mean_loss, grads)`` where ``grads[i]`` is the cotangent sum
    for flat argument ``grad_argnums[i]`` — the gradient of the
    microbatch-mean loss, which for an equal split of a mean loss matches
    the full-batch gradient to fp32 tolerance.
    """
    if loss_ref[0] != "stage":
        raise ValueError(f"loss does not depend on any stage: {loss_ref}")
    n_micro = len(flat_args_per_mb)
    n_stages = len(stages)
    grid = list(gpipe_grid(n_stages, n_micro))
    outs = [[None] * n_stages for _ in range(n_micro)]
    pullbacks = [[None] * n_stages for _ in range(n_micro)]
    for t, s, m in grid:
        ins = [_resolve(r, flat_args_per_mb[m], outs[m])
               for r in stages[s].in_refs]
        ins = _stage_put(stages[s], ins, tick=t, micro=m)
        tr = obs.tracer()
        if tr.enabled and _traceable(ins):
            with tr.span(f"{tick_phase(t, n_stages, n_micro)}:fwd",
                         lane="pipeline", tick=t, stage=s, micro=m):
                outs[m][s], pullbacks[m][s] = jax.vjp(stages[s].fn, *ins)
                jax.block_until_ready(outs[m][s])
        else:
            outs[m][s], pullbacks[m][s] = jax.vjp(stages[s].fn, *ins)

    ls, lj = loss_ref[1], loss_ref[2]
    losses = [outs[m][ls][lj] for m in range(n_micro)]
    mean_loss = sum(losses) / n_micro

    # out_cots[m][s][j]: cotangent for stage s's j-th output, microbatch m
    out_cots = [[[None] * len(outs[m][s]) for s in range(n_stages)]
                for m in range(n_micro)]
    for m in range(n_micro):
        seed = jnp.ones_like(losses[m]) / n_micro
        out_cots[m][ls][lj] = _acc(out_cots[m][ls][lj], seed)
    grads: dict[int, Any] = {i: None for i in grad_argnums}
    for t, s, m in reversed(grid):
        cots = tuple(c if c is not None else _zero_cot(x)
                     for c, x in zip(out_cots[m][s], outs[m][s]))
        tr = obs.tracer()
        if tr.enabled and _traceable(cots):
            with tr.span(f"{tick_phase(t, n_stages, n_micro)}:bwd",
                         lane="pipeline", tick=t, stage=s, micro=m):
                in_cots = pullbacks[m][s](cots)
                jax.block_until_ready(in_cots)
        else:
            in_cots = pullbacks[m][s](cots)
        for ref, c in zip(stages[s].in_refs, in_cots):
            if ref[0] == "stage":
                _, r, j = ref
                out_cots[m][r][j] = _acc(out_cots[m][r][j], c)
            elif ref[0] == "arg" and ref[1] in grads:
                grads[ref[1]] = _acc(grads[ref[1]], c)
    grad_list = [grads[i] if grads[i] is not None
                 else jnp.zeros_like(flat_args_per_mb[0][i])
                 for i in grad_argnums]
    return mean_loss, grad_list


def make_pipelined_fn(stage_fn: Callable, mesh: Mesh, *, axis: str = "pod",
                      n_micro: int = 4, data_axes=("data",)):
    """Wrap ``stage_fn`` into a pipelined callable.

    Inputs: x [n_micro, mb, ...] and stacked stage params [P, ...].
    """
    n_stages = mesh.shape[axis]

    def fn(x, params):
        body = partial(pipeline_forward, stage_fn=stage_fn, axis=axis,
                       n_stages=n_stages, n_micro=n_micro)
        # outputs are broadcast from the last stage via ppermute, so they
        # ARE replicated along the pipe axis — the vma checker cannot
        # prove it statically, hence check_vma=False.
        return jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(), P(axis)),
            out_specs=P(),
            check_vma=False,
        )(x, params)

    return fn
