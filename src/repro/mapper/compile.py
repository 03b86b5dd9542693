"""Compile a placed schedule into jittable, differentiable programs.

The mapper's interpreter (``repro.mapper.executor``) re-walks the jaxpr
equation by equation on every call — eager dispatch that cannot be jitted
or differentiated, which made the mapper a cost abacus rather than an
execution substrate. This module runs the *same* walk, with the *same*
lowering-rule table (``repro.mapper.lowering``), exactly once at trace
time: every placed matmul / im2col conv / eltwise equation is rewritten
into its blocked ``pim_matmul`` / ``pim_mac`` form while JAX traces, and
what comes out is one ordinary JAX function —

    prog = compile_schedule(schedule)     # CompiledProgram, callable
    prog(*args)                           # jitted, zero retrace after 1st
    jax.grad(prog.fn)(*args)              # differentiates through the
                                          # kernels' custom VJPs

so ``Trainer(backend="pim")`` and ``ServeEngine(backend="pim")`` can run
their steps *through the placement* instead of plain ``jax.jit``.

Compiled programs execute **grouped**: each placed node's whole block
grid rides one ``pim_matmul_grouped`` launch (with ``fuse=True``,
independent same-shape placed equations are additionally coalesced
across equation boundaries), so the baked program dispatches roughly one
kernel per placed node instead of one per block — see
``repro.mapper.lowering``. ``placed_blocks`` counts block-level work,
``kernel_launches`` the actual dispatches; the eager interpreter stays
the per-block oracle (``group=False``) and grouped results are
bit-identical to it. Pass ``group=False, fuse=False`` to compile the
legacy one-launch-per-block program (the baseline
``benchmarks/fusion_bench.py`` measures against).

Programs are cached by ``(fn, input avals, placement signature, kernel
knobs)``: compiling the same schedule twice returns the identical
``CompiledProgram`` object, whose ``jax.jit`` cache is already warm —
repeated steps pay zero retrace (asserted via ``trace_count``).

The interpreter remains the oracle: ``CompiledProgram.verify`` checks the
program against both the eager interpreter and ``jax.jit(fn)``.

**Partitioned programs**: when a schedule was built with pipeline
partitions (``build_schedule(..., partitions=K)``),
:func:`compile_partitioned` lowers each partition into its own
:class:`StageProgram` — a jittable function over exactly the values that
cross its boundaries. Stage inputs/outputs are *explicit transfer
points*: each input is tagged with its provenance (a program argument or
an earlier stage's output), so a driver — sequential
(``PartitionedProgram.__call__``) or the GPipe microbatch loop in
``repro.parallel.pipeline`` — can stream activation sets through the
stages without re-deriving dataflow. Running the stages in order is
numerically identical to the unpartitioned program: same equations, same
order, same kernels.
"""

from __future__ import annotations

import collections
import dataclasses
from typing import Any, Callable

import jax
from jax.extend.core import Literal
import numpy as np

from repro import obs
from repro.kernels.mode import resolve_interpret
from repro.mapper import placement as placement_mod
from repro.mapper.lowering import LoweringContext, eval_eqns, eval_placed
from repro.mapper.schedule import Schedule


@dataclasses.dataclass
class CompiledProgram:
    """One schedule lowered to a jittable, differentiable function.

    ``fn`` is the raw traced-replay function (use it under ``jax.grad`` /
    ``jax.vmap`` / your own ``jax.jit``); calling the program invokes the
    pre-jitted version. ``trace_count`` increments each time ``fn``'s body
    runs on tracers (a jit trace/retrace, a grad trace, ...) — calling the
    program with the same avals after warmup must leave it put. Eager
    calls of ``fn`` on concrete arrays are not traces and do not count.
    """

    schedule: Schedule
    fn: Callable
    jitted: Callable
    ctx: LoweringContext
    trace_count: int = 0

    def __call__(self, *args, **kwargs):
        tr = obs.tracer()
        if not tr.enabled:
            # the hot path: byte-identical to calling self.jitted directly
            return self.jitted(*args, **kwargs)
        # compiled programs are one opaque XLA program — the whole call is
        # one execute-lane span (per-node drift comes from measure_drift's
        # eager run); sync so dur covers the dispatched work
        with tr.span("program:call", lane="execute",
                     launches=self.ctx.kernel_launches):
            out = self.jitted(*args, **kwargs)
            jax.block_until_ready(out)
        return out

    @property
    def placed_blocks(self) -> int:
        """Placed block matmuls baked into the program (work, totalled
        over traces)."""
        return self.ctx.placed_blocks

    @property
    def eltwise_calls(self) -> int:
        return self.ctx.eltwise_calls

    @property
    def kernel_launches(self) -> int:
        """Actual ``pallas_call`` dispatches baked into the program
        (grouped/fused launches count once)."""
        return self.ctx.kernel_launches

    @property
    def matmul_launches(self) -> int:
        return self.ctx.matmul_launches

    @property
    def eltwise_launches(self) -> int:
        return self.ctx.eltwise_launches

    def verify(self, *args, rtol: float = 1e-4, atol: float = 1e-4,
               **kwargs) -> float:
        """Check the compiled program against both oracles — the eager
        interpreter and ``jax.jit`` of the original fn. Returns the max
        abs deviation vs ``jax.jit(fn)``."""
        from repro.mapper.executor import ScheduleExecutor

        got = self.jitted(*args, **kwargs)
        interp = ScheduleExecutor(self.schedule, interpret=self.ctx.interpret,
                                  block=self.ctx.block).run(*args, **kwargs)
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(interp)):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=rtol, atol=atol)
        worst = 0.0
        fn = self.schedule.graph.fn
        if fn is not None:
            want = jax.jit(fn)(*args, **kwargs)
            for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
                g, w = np.asarray(g), np.asarray(w)
                np.testing.assert_allclose(g, w, rtol=rtol, atol=atol)
                if g.size:
                    worst = max(worst, float(np.max(np.abs(g - w))))
        return worst


# ---------------------------------------------------------------------------
# program cache
# ---------------------------------------------------------------------------

# LRU-bounded: fn identity is part of the key, so per-call closures (e.g.
# compile_arch's fresh step functions) can never hit — without eviction
# they would pin their schedules and consts forever.
_CACHE: "collections.OrderedDict[tuple, CompiledProgram]" = \
    collections.OrderedDict()
_CACHE_MAX = 32
_STATS = {"hits": 0, "misses": 0}


def _program_key(schedule: Schedule, block: int, interpret: bool,
                 group: bool, fuse: bool, boundaries: tuple = (),
                 devices: tuple = ()) -> tuple:
    closed = schedule.graph.closed_jaxpr
    avals = tuple((tuple(v.aval.shape), str(v.aval.dtype))
                  for v in closed.jaxpr.invars)
    fn = schedule.graph.fn
    fn_key: Any = fn if fn is not None else id(closed)
    # placement.signature() folds in the hierarchy fingerprint (tech +
    # tile/chip geometry), so same-grid placements on different machines
    # get distinct keys; the stage device assignment is part of the key
    # too — same cut on different device rings is a different program
    return (fn_key, avals, schedule.placement.signature(),
            getattr(schedule, "act_bits", 32),
            block, interpret, group, fuse, boundaries,
            tuple(str(d) for d in devices))


def program_cache_stats() -> dict[str, int]:
    return {"hits": _STATS["hits"], "misses": _STATS["misses"],
            "size": len(_CACHE)}


def clear_program_cache() -> None:
    _CACHE.clear()
    _STATS["hits"] = _STATS["misses"] = 0


# ---------------------------------------------------------------------------
# the compiler
# ---------------------------------------------------------------------------


def compile_schedule(schedule: Schedule, *, block: int = 128,
                     interpret: bool | None = None, group: bool = True,
                     fuse: bool = True,
                     use_cache: bool = True) -> CompiledProgram:
    """Lower ``schedule`` into one jittable, differentiable function.

    The returned :class:`CompiledProgram` is callable with exactly the
    arguments the schedule's fn was traced with (pytrees welcome). The
    first call traces once — the Python jaxpr walk runs under the trace
    and bakes every placed node's grouped kernel launch (one per node;
    fewer with ``fuse``) into a single XLA program; subsequent same-shape
    calls replay the compiled executable. ``group=False, fuse=False``
    bakes the legacy one-launch-per-block program instead.
    """
    interpret = resolve_interpret(interpret)
    if use_cache:
        key = _program_key(schedule, block, interpret, group, fuse)
        hit = _CACHE.get(key)
        if hit is not None:
            _STATS["hits"] += 1
            obs.metrics().counter("compile.cache_hits").inc()
            _CACHE.move_to_end(key)
            return hit
        _STATS["misses"] += 1
        obs.metrics().counter("compile.cache_misses").inc()

    with obs.span("compile:schedule", lane="compile"), \
            obs.mapper_phase("compile_schedule"):
        program = _compile_schedule(schedule, block, interpret, group, fuse)
    if use_cache:
        _CACHE[key] = program
        while len(_CACHE) > _CACHE_MAX:
            _CACHE.popitem(last=False)
    return program


def _compile_schedule(schedule: Schedule, block: int, interpret: bool,
                      group: bool, fuse: bool) -> CompiledProgram:
    """The program object itself; its jit traces on the first call."""
    ctx = LoweringContext(schedule, block=block, interpret=interpret,
                          group=group, fuse=fuse)
    closed = schedule.graph.closed_jaxpr
    in_tree = schedule.graph.in_tree
    out_tree = schedule.graph.out_tree
    holder: list[CompiledProgram] = []

    def fn(*args, **kwargs):
        flat, tree = jax.tree.flatten((args, kwargs))
        if holder and any(isinstance(x, jax.core.Tracer) for x in flat):
            holder[0].trace_count += 1
            obs.metrics().counter("compile.traces").inc()
            tr = obs.tracer()
            if tr.enabled:
                # trace-time walk: record it on the compile lane — the
                # span surrounds the jaxpr replay that bakes the kernels
                with tr.span("trace:program", lane="compile",
                             trace=holder[0].trace_count):
                    if in_tree is not None and tree != in_tree:
                        raise TypeError(
                            f"argument structure {tree} != traced "
                            f"structure {in_tree}")
                    outs = eval_placed(ctx, closed.jaxpr, closed.consts,
                                       flat)
                return (jax.tree.unflatten(out_tree, outs) if out_tree
                        else outs)
        if in_tree is not None and tree != in_tree:
            raise TypeError(f"argument structure {tree} != traced "
                            f"structure {in_tree}")
        outs = eval_placed(ctx, closed.jaxpr, closed.consts, flat)
        return jax.tree.unflatten(out_tree, outs) if out_tree else outs

    program = CompiledProgram(schedule=schedule, fn=fn, jitted=jax.jit(fn),
                              ctx=ctx)
    holder.append(program)
    return program


# ---------------------------------------------------------------------------
# partitioned programs (one jittable stage per pipeline partition)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class StageProgram:
    """One pipeline partition lowered to a jittable function.

    ``fn(*invals) -> tuple(outvals)`` evaluates exactly this partition's
    top-level equations (through the shared lowering-rule table, so placed
    matmuls run as blocked PIM kernel calls). ``in_refs[i]`` names where
    input ``i`` comes from — ``("arg", flat_idx)`` for a program argument
    or ``("stage", s, j)`` for output ``j`` of an earlier stage — making
    every inter-stage transfer explicit for the microbatch driver.
    """

    idx: int
    fn: Callable
    jitted: Callable
    in_refs: tuple[tuple, ...]
    n_outs: int
    out_bits: int                 # activation bits this stage streams out
    device: Any = None            # pinned JAX device (None = unpinned):
                                  # drivers device_put inputs here (non-
                                  # blocking) and jit follows the committed
                                  # inputs onto the stage's own async queue


def _resolve(ref: tuple, flat: list, stage_outs: list):
    """Value behind a transfer reference: ``("arg", i)`` is program
    argument i, ``("stage", s, j)`` output j of stage s, ``("lit", v)``
    a literal."""
    if ref[0] == "arg":
        return flat[ref[1]]
    if ref[0] == "stage":
        return stage_outs[ref[1]][ref[2]]
    return ref[1]


@dataclasses.dataclass
class PartitionedProgram:
    """A schedule compiled as one jittable program per pipeline partition.

    Calling the program runs the stages in order inside one ``jax.jit`` —
    numerically identical to the unpartitioned ``CompiledProgram`` (same
    equations, same kernels, same order). The stage list is the real
    pipeline surface: ``repro.parallel.pipeline`` streams microbatches
    through ``stages`` with GPipe fill/drain and differentiates them
    per-stage with ``jax.vjp``.
    """

    schedule: Schedule
    partitions: list
    stages: list[StageProgram]
    out_refs: tuple[tuple, ...]
    ctx: LoweringContext
    fn: Callable = None
    jitted: Callable = None
    trace_count: int = 0          # whole-program traces (jit/grad)
    stage_trace_count: int = 0    # per-stage body traces (gpipe driver)

    def __call__(self, *args, **kwargs):
        tr = obs.tracer()
        if not tr.enabled:
            return self.jitted(*args, **kwargs)
        with tr.span("program:call", lane="execute",
                     partitions=len(self.stages)):
            out = self.jitted(*args, **kwargs)
            jax.block_until_ready(out)
        return out

    @property
    def n_partitions(self) -> int:
        return len(self.stages)

    @property
    def devices(self) -> tuple:
        """Per-stage pinned devices (``None`` entries = unpinned)."""
        return tuple(st.device for st in self.stages)

    def run_async(self, *args, **kwargs):
        """Run the stages in order with non-blocking ``device_put``
        transfers at the cut points, without jitting the chain as a whole
        — each pinned stage executes on its own device, and nothing
        blocks, so JAX async dispatch overlaps this call with whatever
        the caller does next. Token/loss outputs are bit-identical to
        ``self(*args)`` (same stage programs, same order); callers
        observe values (or ``jax.block_until_ready``) to sync."""
        flat = self.flatten_args(*args, **kwargs)
        stage_outs = self._run_stages_async(flat)
        return self.unflatten_outs([_resolve(r, flat, stage_outs)
                                    for r in self.out_refs])

    def run_stages_async(self, *args, **kwargs) -> list[tuple]:
        """:meth:`run_async`, returning each stage's own outputs (stage
        order) instead of the program's — each lives on its stage's
        pinned device, which is how a caller checks the placement."""
        return self._run_stages_async(self.flatten_args(*args, **kwargs))

    def _run_stages_async(self, flat: list) -> list[tuple]:
        stage_outs: list[tuple] = []
        for st in self.stages:
            ins = [_resolve(r, flat, stage_outs) for r in st.in_refs]
            if st.device is not None:
                ins = [jax.device_put(x, st.device) for x in ins]
            stage_outs.append(st.jitted(*ins))
        return stage_outs

    @property
    def placed_blocks(self) -> int:
        return self.ctx.placed_blocks

    @property
    def eltwise_calls(self) -> int:
        return self.ctx.eltwise_calls

    @property
    def kernel_launches(self) -> int:
        return self.ctx.kernel_launches

    @property
    def matmul_launches(self) -> int:
        return self.ctx.matmul_launches

    @property
    def eltwise_launches(self) -> int:
        return self.ctx.eltwise_launches

    def flatten_args(self, *args, **kwargs) -> list:
        """Flatten a call's arguments exactly like the program does,
        checking the traced pytree structure — drivers use this to build
        the per-microbatch flat argument lists the stage ``in_refs``
        index into."""
        flat, tree = jax.tree.flatten((args, kwargs))
        in_tree = self.schedule.graph.in_tree
        if in_tree is not None and tree != in_tree:
            raise TypeError(f"argument structure {tree} != traced "
                            f"structure {in_tree}")
        return flat

    def unflatten_outs(self, out_flat: list):
        out_tree = self.schedule.graph.out_tree
        return (jax.tree.unflatten(out_tree, out_flat) if out_tree
                else out_flat)

    def verify(self, *args, rtol: float = 1e-4, atol: float = 1e-4,
               **kwargs) -> float:
        """Check the partitioned program against ``jax.jit(fn)``."""
        got = self.jitted(*args, **kwargs)
        worst = 0.0
        fn = self.schedule.graph.fn
        assert fn is not None, "graph was built without a fn reference"
        want = jax.jit(fn)(*args, **kwargs)
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            g, w = np.asarray(g), np.asarray(w)
            np.testing.assert_allclose(g, w, rtol=rtol, atol=atol)
            if g.size:
                worst = max(worst, float(np.max(np.abs(g - w))))
        return worst


def _aval_bits(v) -> int:
    return int(np.prod(v.aval.shape, dtype=np.int64)) * v.aval.dtype.itemsize * 8


def compile_partitioned(schedule: Schedule, *,
                        partitions: int | None = None, block: int = 128,
                        interpret: bool | None = None, group: bool = True,
                        fuse: bool = True, use_cache: bool = True,
                        devices=None) -> PartitionedProgram:
    """Lower ``schedule`` into one jittable program per pipeline partition.

    Uses the partitions the schedule was built with
    (``build_schedule(..., partitions=K)``); pass ``partitions=K`` to cut
    here instead. Each stage program consumes exactly the values crossing
    its upstream boundary (tagged with provenance) and returns the values
    crossing its downstream boundary — the explicit transfer points the
    microbatch pipeline driver streams.

    ``devices`` (a sequence of JAX devices) pins stage ``i`` to
    ``devices[i % len(devices)]``: the async drivers
    (``PartitionedProgram.run_async``,
    ``repro.parallel.pipeline.run_partitioned_async``) then route each
    stage's inputs there with non-blocking ``device_put`` so stages
    execute concurrently on their own device queues. Force N host devices
    locally with ``XLA_FLAGS=--xla_force_host_platform_device_count=N``.
    """
    parts = schedule.partitions
    if partitions is not None:
        parts = placement_mod.partition(schedule.graph, partitions)
    if not parts:
        raise ValueError(
            "schedule has no pipeline partitions; build it with "
            "build_schedule(..., partitions=K) or pass partitions=K")
    boundaries = tuple((p.eqn_start, p.eqn_end) for p in parts)
    dev_ring = tuple(devices) if devices else ()
    interpret = resolve_interpret(interpret)

    if use_cache:
        key = _program_key(schedule, block, interpret, group, fuse,
                           boundaries, dev_ring)
        hit = _CACHE.get(key)
        if hit is not None and isinstance(hit, PartitionedProgram):
            _STATS["hits"] += 1
            obs.metrics().counter("compile.cache_hits").inc()
            _CACHE.move_to_end(key)
            return hit
        _STATS["misses"] += 1
        obs.metrics().counter("compile.cache_misses").inc()

    ctx = LoweringContext(schedule, block=block, interpret=interpret,
                          group=group, fuse=fuse)
    closed = schedule.graph.closed_jaxpr
    jaxpr = closed.jaxpr
    consts_by_var = dict(zip(jaxpr.constvars, closed.consts))
    invar_idx = {v: i for i, v in enumerate(jaxpr.invars)}

    produced_by: dict[Any, tuple[int, int]] = {}   # var -> (stage, out_idx)
    # last top-level eqn index reading each var (len(eqns) if returned)
    last_read: dict[Any, int] = {}
    for e, eqn in enumerate(jaxpr.eqns):
        for v in eqn.invars:
            if not isinstance(v, Literal):
                last_read[v] = e
    for v in jaxpr.outvars:
        if not isinstance(v, Literal):
            last_read[v] = len(jaxpr.eqns)

    holder: list[PartitionedProgram] = []
    stages: list[StageProgram] = []
    for p in parts:
        eqns = jaxpr.eqns[p.eqn_start:p.eqn_end]
        inner_prod = {v for eqn in eqns for v in eqn.outvars
                      if not isinstance(v, jax.core.DropVar)}
        in_vars: list = []
        stage_consts: dict = {}
        for eqn in eqns:
            for v in eqn.invars:
                if isinstance(v, Literal) or v in inner_prod:
                    continue
                if v in consts_by_var:
                    stage_consts[v] = consts_by_var[v]
                elif v not in in_vars:
                    in_vars.append(v)
        out_vars = [v for eqn in eqns for v in eqn.outvars
                    if not isinstance(v, jax.core.DropVar)
                    and last_read.get(v, -1) >= p.eqn_end]
        in_refs = []
        for v in in_vars:
            if v in invar_idx:
                in_refs.append(("arg", invar_idx[v]))
            else:
                in_refs.append(("stage", *produced_by[v]))
        for j, v in enumerate(out_vars):
            produced_by[v] = (p.idx, j)

        def stage_fn(*invals, _eqns=eqns, _ins=tuple(in_vars),
                     _outs=tuple(out_vars), _consts=dict(stage_consts)):
            if holder and any(isinstance(x, jax.core.Tracer)
                              for x in invals):
                holder[0].stage_trace_count += 1
            env = dict(_consts)
            env.update(zip(_ins, invals))
            eval_eqns(ctx, _eqns, env)
            return tuple(env[v] for v in _outs)

        stages.append(StageProgram(
            idx=p.idx, fn=stage_fn, jitted=jax.jit(stage_fn),
            in_refs=tuple(in_refs), n_outs=len(out_vars),
            out_bits=sum(_aval_bits(v) for v in out_vars),
            device=dev_ring[p.idx % len(dev_ring)] if dev_ring else None))

    out_refs: list[tuple] = []
    for v in jaxpr.outvars:
        if isinstance(v, Literal):
            out_refs.append(("lit", v.val))
        elif v in invar_idx:
            out_refs.append(("arg", invar_idx[v]))
        else:
            out_refs.append(("stage", *produced_by[v]))

    program = PartitionedProgram(schedule=schedule, partitions=list(parts),
                                 stages=stages, out_refs=tuple(out_refs),
                                 ctx=ctx)

    def fn(*args, **kwargs):
        flat = program.flatten_args(*args, **kwargs)
        if holder and any(isinstance(x, jax.core.Tracer) for x in flat):
            holder[0].trace_count += 1
        stage_outs: list[tuple] = []
        for st in stages:
            stage_outs.append(st.fn(*[_resolve(r, flat, stage_outs)
                                      for r in st.in_refs]))
        return program.unflatten_outs([_resolve(r, flat, stage_outs)
                                       for r in out_refs])

    program.fn = fn
    program.jitted = jax.jit(fn)
    holder.append(program)
    if use_cache:
        _CACHE[key] = program
        while len(_CACHE) > _CACHE_MAX:
            _CACHE.popitem(last=False)
    return program
