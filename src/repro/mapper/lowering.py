"""The one lowering-rule table: placed jaxpr equations -> PIM kernel calls.

Both execution modes of a :class:`~repro.mapper.schedule.Schedule` share
this module, so the matmul/conv/eltwise lowering logic exists exactly once:

  * the **interpreter** (``repro.mapper.executor``) calls
    :func:`eval_placed` with concrete arrays — eager per-equation dispatch
    with ``group=False``: one ``pim_matmul`` launch **per placed block**,
    the debugging/verification mode and the bit-level oracle;
  * the **compiler** (``repro.mapper.compile``) calls the same
    :func:`eval_placed` with tracers under ``jax.jit`` and ``group=True``:
    the Python walk runs once at trace time and each placed node's whole
    block grid is stacked into **one** ``pim_matmul_grouped`` launch — the
    paper's subarrays computing all placed blocks in parallel, instead of
    an O(blocks) chain of launches and scatter-adds.

Grouped execution is constructed to be *bit-identical* to the per-block
oracle: every group accumulates its K axis with the same tile sizes and
order a standalone ``pim_matmul`` would, extra zero-padding contributes
exact fp zeros, and the cross-row-block reduction is an explicit
ascending left-fold — the same association order as the oracle's
scatter-add chain.

With ``fuse=True`` (the compiler default) the walk additionally coalesces
*independent* placed equations across equation boundaries: same-shape
placed matmuls whose operands are all already computed ride one grouped
launch (q/k/v-projection style), and whole waves of ready eltwise
add/sub/mul equations (optimizer updates across parameter leaves) ride
one ``pim_mac_grouped`` launch. Fusion only ever *reorders* equations
whose inputs were already available, so values are unchanged.

``placed_blocks`` counts block-level work, ``kernel_launches`` counts
actual ``pallas_call`` dispatches — under the per-block oracle they are
equal (plus eltwise); under grouped execution launches collapse to
roughly one per placed node.

When the schedule's subarray grid stores sub-fp32 weights
(``weight_dtype`` of ``int8`` / ``fp8_e4m3`` / ``fp8_e5m2`` / ``fp16``),
the stationary matmul operand is quantized blockwise per output column
(``repro.core.quant.quantize_ste``) and the grouped launch dequantizes
on load (``pim_matmul_grouped_q`` — scales ride as a per-(group, column)
operand). Accumulation stays fp32, gradients flow straight-through, and
the per-block oracle applies the identical quantize→dequantize to each
padded block, so grouped and oracle modes remain bit-identical.

Rules are keyed by the node kind from ``repro.core.estimator.NODE_KINDS``
(the shared registry); a rule returns the lowered outputs or ``None`` to
decline, in which case the equation falls back to ``primitive.bind`` —
numerically exact, just not routed through the PIM kernels.

Fallback cases: batched/multi-contraction dot_generals, grouped/dilated/
negative-padding convs, non-NHWC conv layouts, div (a*(1/b) would diverge
from lax.div at the overflow edge), integer matmuls (would round past
2^24), and placed ops inside scan/while bodies. Call-like primitives
(pjit, remat, custom_vjp, ...) are inlined only when placed nodes live
inside them; otherwise they are bound as-is, which preserves the
caller's custom differentiation rules under ``jax.grad`` of a compiled
program.

Caveat of that inlining: when a ``custom_vjp`` body *does* contain placed
nodes, differentiating the compiled program autodiffs the inlined primal
(through the PIM kernels' own VJPs) instead of invoking the registered
backward — correct only when that backward is mathematically the
gradient of the primal, which holds for this repo's custom VJPs
(recompute-for-memory patterns) but not for e.g. straight-through
estimators. Likewise an inlined ``jax.checkpoint`` body loses its
rematerialization (a memory property, not a numerics one). The grad
tests in tests/test_compile.py pin the supported surface.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
from jax.extend.core import Literal
import jax.numpy as jnp

from repro import obs
from repro.core import estimator
from repro.core import quant
from repro.core.estimator import CALL_PRIMS, inner_jaxpr
from repro.kernels.mode import resolve_interpret
from repro.kernels.pim_mac import (pim_mac, pim_mac_grouped, pim_matmul,
                                   pim_matmul_grouped, pim_matmul_grouped_q)


def _pad_to(x: jnp.ndarray, mults: tuple[int, int]) -> jnp.ndarray:
    pr = (-x.shape[0]) % mults[0]
    pc = (-x.shape[1]) % mults[1]
    if pr or pc:
        x = jnp.pad(x, ((0, pr), (0, pc)))
    return x


@dataclasses.dataclass
class LoweringContext:
    """Schedule + kernel knobs + call counters, threaded through the rules.

    ``group=False`` is the per-block oracle (one launch per placed block,
    the interpreter's mode); ``group=True`` stacks each node's blocks into
    one grouped launch. ``fuse=True`` additionally coalesces independent
    same-shape placed equations across equation boundaries (requires
    ``group=True``; the compiler's mode).

    Counters: ``placed_blocks`` / ``eltwise_calls`` count kernel-routed
    *work* (block matmuls resp. eltwise equations); ``matmul_launches``
    / ``eltwise_launches`` count actual ``pallas_call`` dispatches per
    kind, with ``kernel_launches`` their sum. Under the interpreter they
    count per run; under the compiler they count per *trace* (the kernel
    calls baked into the program).
    """

    schedule: Any                 # repro.mapper.schedule.Schedule
    block: int = 128              # pallas tile edge (pad-to multiple)
    interpret: bool | None = None   # None: compiled on a TPU only
    group: bool = True            # grouped launches (False = per-block)
    fuse: bool = True             # cross-equation coalescing
    weight_dtype: str | None = None  # default: the schedule's subarray grid
    placed_blocks: int = 0
    eltwise_calls: int = 0
    matmul_launches: int = 0
    eltwise_launches: int = 0

    def __post_init__(self):
        self.interpret = resolve_interpret(self.interpret)
        self.node_by_eqn = {nd.eqn_id: nd
                            for nd in self.schedule.graph.nodes}
        self._subtree_cache: dict[int, bool] = {}
        if self.weight_dtype is None:
            self.weight_dtype = getattr(self.schedule.hierarchy.subarray,
                                        "weight_dtype", "fp32")

    @property
    def kernel_launches(self) -> int:
        """All ``pallas_call`` dispatches (matmul + eltwise)."""
        return self.matmul_launches + self.eltwise_launches

    def subtree_has_placed(self, jaxpr) -> bool:
        """True if any equation reachable from ``jaxpr`` is a graph node."""
        key = id(jaxpr)
        if key not in self._subtree_cache:
            self._subtree_cache[key] = any(
                id(eqn) in self.node_by_eqn
                for eqn, _ in estimator.iter_eqns(jaxpr))
        return self._subtree_cache[key]


# ---------------------------------------------------------------------------
# placed matmul (shared by the dot_general and conv rules)
# ---------------------------------------------------------------------------


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _grouped_operands(ctx: LoweringContext, node_idx: int, a2, b2):
    """Pad once and stack a node's placed block operands.

    The node's stationary weight is a (row_blocks x col_blocks) grid of
    subarray-sized blocks; this builds the stacked grouped operands
    ``a_g (R, mp, Kb)`` (one activation slab per *row* chunk — the kernel
    fans each slab out to its C column groups through the shared-A index
    map, so activations are never replicated) and ``b_g (R*C, Kb, Nb)``
    (replica 0 — replicas are throughput copies holding identical
    weights), padded to ``ctx.block`` multiples exactly as the per-block
    path pads each block. Returns ``(a_g, b_g, meta)``; ``meta`` feeds
    :func:`_grouped_reduce`.
    """
    np_ = ctx.schedule.placement.node_placements[node_idx]
    sub = ctx.schedule.hierarchy.subarray
    br, bc = sub.weight_rows, sub.weight_cols
    R, C = np_.row_blocks, np_.col_blocks
    m, k = a2.shape
    n = b2.shape[1]
    blk = ctx.block
    h = br if R > 1 else k            # per-row-chunk height (values)
    w = bc if C > 1 else n            # per-col-chunk width (values)
    mp, kb, nb = _round_up(m, blk), _round_up(h, blk), _round_up(w, blk)
    a2 = a2.astype(jnp.float32)
    b2 = b2.astype(jnp.float32)
    if mp - m or R * h - k:
        a2 = jnp.pad(a2, ((0, mp - m), (0, R * h - k)))
    a_ch = jnp.moveaxis(a2.reshape(mp, R, h), 1, 0)       # (R, mp, h)
    if kb - h:
        a_ch = jnp.pad(a_ch, ((0, 0), (0, 0), (0, kb - h)))
    if R * h - k or C * w - n:
        b2 = jnp.pad(b2, ((0, R * h - k), (0, C * w - n)))
    b_ch = b2.reshape(R, h, C, w).transpose(0, 2, 1, 3)   # (R, C, h, w)
    if kb - h or nb - w:
        b_ch = jnp.pad(b_ch, ((0, 0), (0, 0), (0, kb - h), (0, nb - w)))
    b_g = b_ch.reshape(R * C, kb, nb)
    return a_ch, b_g, (R, C, m, n, w)


def _grouped_reduce(out_g: jnp.ndarray, meta) -> jnp.ndarray:
    """(G, mp, Nb) grouped partial products -> (m, n): one segment-sum
    over the row-block axis per output column-block, then stitch the
    column blocks. The fold is explicit and ascending so the result is
    bit-identical to the oracle's per-block scatter-add chain."""
    R, C, m, n, w = meta
    out4 = out_g.reshape(R, C, out_g.shape[1], out_g.shape[2])
    col = out4[0]
    for i in range(1, R):
        col = col + out4[i]
    col = col[:, :m, :w]                                   # (C, m, w)
    return jnp.swapaxes(col, 0, 1).reshape(m, C * w)[:, :n]


def _observe_quant_error(ctx: LoweringContext, b_g, q, s) -> None:
    """Record the launch's per-layer quantization error (max over columns
    of |deq - w| relative to the column absmax) into the obs histogram.
    Eager mode only — under jit tracing operands are Tracers and nothing
    is recorded, so compiled programs stay byte-identical."""
    if any(isinstance(x, jax.core.Tracer) for x in (b_g, q, s)):
        return
    qmax = quant.spec(ctx.weight_dtype).qmax
    rel = float(jnp.max(jnp.abs(q * s - b_g) / (s * qmax)))
    obs.metrics().histogram("pim.quant_layer_rel_error").observe(rel)


def _launch_grouped(ctx: LoweringContext, a_g, b_g,
                    col_groups: int) -> jnp.ndarray:
    """One grouped launch over stacked block operands, quantizing the
    stationary side first when the schedule's weight grid is sub-fp32.

    Scales are per (group, output-column) — ``quantize_ste`` keeps fp32
    gradient flow — and ``pim_matmul_grouped_q`` dequantizes on load, so
    results are bit-identical to the per-block oracle storing the same
    grid (identical per-column scales: zero padding never moves a
    column's absmax)."""
    if ctx.weight_dtype != "fp32":
        q, s = quant.quantize_ste(b_g, ctx.weight_dtype, 1)
        _observe_quant_error(ctx, b_g, q, s)
        out_g = pim_matmul_grouped_q(a_g, q, s, bm=ctx.block, bn=ctx.block,
                                     bk=ctx.block, interpret=ctx.interpret,
                                     col_groups=col_groups)
    else:
        out_g = pim_matmul_grouped(a_g, b_g, bm=ctx.block, bn=ctx.block,
                                   bk=ctx.block, interpret=ctx.interpret,
                                   col_groups=col_groups)
    ctx.placed_blocks += b_g.shape[0]
    ctx.matmul_launches += 1
    return out_g


def blocked_matmul(ctx: LoweringContext, node_idx: int, a2: jnp.ndarray,
                   b2: jnp.ndarray) -> jnp.ndarray:
    """A (m,k) @ B (k,n) through the node's placed block grid — replica 0;
    replicas are throughput copies holding identical weights.

    ``ctx.group=True``: one ``pim_matmul_grouped`` launch over the stacked
    blocks + a single segment-sum per output column-block.
    ``ctx.group=False``: the per-block oracle — one ``pim_matmul`` launch
    per placed block, partial products scatter-added in block order.
    Sub-fp32 weight grids quantize the stationary operand per placed
    block column in both modes (same scales, bit-identical results).
    """
    if ctx.group:
        a_g, b_g, meta = _grouped_operands(ctx, node_idx, a2, b2)
        out_g = _launch_grouped(ctx, a_g, b_g, meta[1])
        return _grouped_reduce(out_g, meta)

    np_ = ctx.schedule.placement.node_placements[node_idx]
    m, _ = a2.shape
    _, n = b2.shape
    out = jnp.zeros((m, n), jnp.float32)
    for blk in np_.iter_blocks(ctx.schedule.hierarchy, replica=0):
        pa = _pad_to(a2[:, blk.row0:blk.row0 + blk.n_rows],
                     (ctx.block, ctx.block))
        pb = _pad_to(b2[blk.row0:blk.row0 + blk.n_rows,
                        blk.col0:blk.col0 + blk.n_cols],
                     (ctx.block, ctx.block)).astype(jnp.float32)
        if ctx.weight_dtype != "fp32":
            qb, sb = quant.quantize_ste(pb, ctx.weight_dtype, 0)
            pb = qb * sb              # the block's stored grid, dequantized
        part = pim_matmul(pa.astype(jnp.float32), pb,
                          bm=ctx.block, bn=ctx.block, bk=ctx.block,
                          interpret=ctx.interpret)
        out = out.at[:, blk.col0:blk.col0 + blk.n_cols].add(
            part[:m, :blk.n_cols])
        ctx.placed_blocks += 1
        ctx.matmul_launches += 1
    return out


# ---------------------------------------------------------------------------
# per-kind rules
# ---------------------------------------------------------------------------


def _dot_operands(eqn, invals):
    """(a2, b2) 2-D operands of a lowerable ``dot_general``, else None.
    Shared by :func:`lower_dot` and the cross-equation fusion scanner."""
    lhs, rhs = invals
    aval = eqn.outvars[0].aval
    if not jnp.issubdtype(aval.dtype, jnp.floating):
        return None              # int matmuls would round past 2^24
    (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
    if lb or rb or len(lc) != 1 or rhs.ndim != 2:
        return None
    if lhs.ndim == 2:
        a2 = lhs if lc[0] == 1 else lhs.T
    elif lc[0] == lhs.ndim - 1:
        # x @ W with leading activation dims (the transformer case,
        # (B, S, d) @ (d, n)): fold them into m — that is exactly how the
        # placement sized this node's stationary (k, n) weight
        a2 = lhs.reshape(-1, lhs.shape[-1])
    else:
        return None
    b2 = rhs if rc[0] == 0 else rhs.T
    return a2, b2


def lower_dot(ctx: LoweringContext, eqn, node, invals):
    ops = _dot_operands(eqn, invals)
    if ops is None:
        return None
    aval = eqn.outvars[0].aval
    out = blocked_matmul(ctx, node.idx, *ops)
    return [out.reshape(aval.shape).astype(aval.dtype)]


def lower_conv(ctx: LoweringContext, eqn, node, invals):
    x, w = invals
    if not jnp.issubdtype(eqn.outvars[0].aval.dtype, jnp.floating):
        return None
    p = eqn.params
    dn = p["dimension_numbers"]
    if (dn.lhs_spec != (0, 3, 1, 2) or dn.rhs_spec != (3, 2, 0, 1)
            or dn.out_spec != (0, 3, 1, 2)):
        return None              # only NHWC / HWIO / NHWC
    if (p.get("feature_group_count", 1) != 1
            or p.get("batch_group_count", 1) != 1
            or any(d != 1 for d in p["lhs_dilation"])
            or any(d != 1 for d in p["rhs_dilation"])
            or any(pad < 0 for pair in p["padding"] for pad in pair)):
        return None              # negative padding: numeric fallback
    kh, kw, cin, cout = w.shape
    sh, sw = p["window_strides"]
    (pt, pb_), (pl, pr) = p["padding"]
    xp = jnp.pad(x, ((0, 0), (pt, pb_), (pl, pr), (0, 0)))
    n, hh, ww, _ = xp.shape
    oh = (hh - kh) // sh + 1
    ow = (ww - kw) // sw + 1
    # im2col: patch layout (kh, kw, cin) matches HWIO.reshape(-1, cout)
    cols = [xp[:, i:i + oh * sh:sh, j:j + ow * sw:sw, :]
            for i in range(kh) for j in range(kw)]
    a2 = jnp.concatenate(cols, axis=-1).reshape(n * oh * ow, kh * kw * cin)
    b2 = w.reshape(kh * kw * cin, cout)
    out = blocked_matmul(ctx, node.idx, a2, b2)
    out = out.reshape(n, oh, ow, cout)
    return [out.astype(eqn.outvars[0].aval.dtype)]


def _eltwise_operands(eqn, node, invals):
    """``(a, b, acc)`` with out = acc + a*b for a lowerable eltwise
    equation, broadcasts resolved, else None. Shared by
    :func:`lower_eltwise` and the eltwise fusion scanner."""
    if len(invals) != 2:
        return None          # unary prims registered via register_node_kind
    a, b = invals
    aval = eqn.outvars[0].aval
    if not jnp.issubdtype(aval.dtype, jnp.floating) or not aval.size:
        return None
    # lax eltwise prims broadcast size-1 dims; resolve before pim_mac
    a = jnp.broadcast_to(jnp.asarray(a, aval.dtype), aval.shape)
    b = jnp.broadcast_to(jnp.asarray(b, aval.dtype), aval.shape)
    one = jnp.ones_like(a)
    op = node.op
    if op == "add":        # b + a*1
        return a, one, b
    if op == "sub":        # a + b*(-1)
        return b, -one, a
    if op == "mul":        # 0 + a*b
        return a, b, jnp.zeros_like(a)
    # div as a*(1/b) diverges from lax.div when 1/b overflows or
    # rounds; keep the jit-match contract via the numeric fallback
    return None


def lower_eltwise(ctx: LoweringContext, eqn, node, invals):
    triple = _eltwise_operands(eqn, node, invals)
    if triple is None:
        return None
    a, b, acc = triple
    out = pim_mac(a, b, acc, interpret=ctx.interpret)
    ctx.eltwise_calls += 1
    ctx.eltwise_launches += 1
    return [out.astype(eqn.outvars[0].aval.dtype)]


# keyed by the estimator registry's node kinds — one rule per kind
RULES: dict[str, Callable] = {
    "matmul": lower_dot,
    "conv": lower_conv,
    "eltwise": lower_eltwise,
}

assert set(RULES) == set(estimator.NODE_KINDS.values()), (
    "lowering rules out of sync with estimator.NODE_KINDS")


# ---------------------------------------------------------------------------
# cross-equation fusion (compiler mode): coalesce independent placed
# equations whose operands are all already computed into one launch
# ---------------------------------------------------------------------------


def _dot_meta(eqn):
    """Shape/dnums/dtype signature deciding fusability from eqn metadata
    alone — equal signatures (given an accepted lead) guarantee
    ``_dot_operands`` succeeds with identically-shaped operands, so the
    scanner never builds traced operands for rejected candidates."""
    return (tuple(eqn.invars[0].aval.shape), tuple(eqn.invars[1].aval.shape),
            eqn.params["dimension_numbers"], eqn.outvars[0].aval.dtype)


def _fuse_matmuls(ctx: LoweringContext, lead, peers, env, fused, read,
                  ready, node, invals):
    """Coalesce the placed matmul ``lead`` with every *later* placed
    matmul equation (``peers``, the pre-filtered candidate tail) that
    (a) has no pending data dependence (all invars already computed —
    mutual independence follows), and (b) lowers to the same stacked
    block-grid shape. Returns the leader's outputs after writing the
    peers' outputs into ``env``, or None to decline."""
    ops = _dot_operands(lead, invals)
    if ops is None:
        return None
    placements = ctx.schedule.placement.node_placements
    np0 = placements[node.idx]
    key = (_dot_meta(lead), np0.row_blocks, np0.col_blocks)
    group = [(lead, node, ops)]
    for e2 in peers:
        if id(e2) in fused or not ready(e2):
            continue
        nd2 = ctx.node_by_eqn[id(e2)]
        np2 = placements.get(nd2.idx)
        if np2 is None or (_dot_meta(e2), np2.row_blocks,
                           np2.col_blocks) != key:
            continue
        group.append((e2, nd2,
                      _dot_operands(e2, [read(v) for v in e2.invars])))
    if len(group) == 1:
        return None                  # nothing to fuse; plain grouped rule
    stacked = [_grouped_operands(ctx, nd.idx, a2, b2)
               for _, nd, (a2, b2) in group]
    g_per = stacked[0][1].shape[0]
    cols = stacked[0][2][1]          # shared C (same block grid by key)
    a_all = jnp.concatenate([s[0] for s in stacked])
    b_all = jnp.concatenate([s[1] for s in stacked])
    out_all = _launch_grouped(ctx, a_all, b_all, cols)
    outs0 = None
    for i, ((e2, _, _), (_, _, meta)) in enumerate(zip(group, stacked)):
        out = _grouped_reduce(out_all[i * g_per:(i + 1) * g_per], meta)
        aval = e2.outvars[0].aval
        lowered = [out.reshape(aval.shape).astype(aval.dtype)]
        if i == 0:
            outs0 = lowered
        else:
            env.update(zip(e2.outvars, lowered, strict=True))
            fused.add(id(e2))
    return outs0


def _fuse_eltwise(ctx: LoweringContext, lead, peers, env, fused, read,
                  ready, node, invals):
    """Coalesce the whole *ready wave* of eltwise equations starting at
    ``lead`` — every later add/sub/mul (``peers``, the pre-filtered
    candidate tail) whose operands are already computed (optimizer
    updates across parameter leaves are the classic case) — into a
    single ragged ``pim_mac_grouped`` launch."""
    triple = _eltwise_operands(lead, node, invals)
    if triple is None:
        return None
    dtype = lead.outvars[0].aval.dtype
    group = [(lead, triple)]
    for e2 in peers:
        if id(e2) in fused or not ready(e2):
            continue
        nd2 = ctx.node_by_eqn[id(e2)]
        # metadata-only acceptance: operands are built for members, never
        # for rejected candidates (no dead traced broadcasts/ones/zeros)
        aval2 = e2.outvars[0].aval
        if (len(e2.invars) != 2 or aval2.dtype != dtype or not aval2.size
                or nd2.op not in ("add", "sub", "mul")):
            continue
        group.append((e2, _eltwise_operands(e2, nd2,
                                            [read(v) for v in e2.invars])))
    if len(group) == 1:
        return None
    outs = pim_mac_grouped([t for _, t in group], interpret=ctx.interpret)
    ctx.eltwise_calls += len(group)
    ctx.eltwise_launches += 1
    outs0 = None
    for i, ((e2, _), out) in enumerate(zip(group, outs)):
        lowered = [out.astype(e2.outvars[0].aval.dtype)]
        if i == 0:
            outs0 = lowered
        else:
            env.update(zip(e2.outvars, lowered, strict=True))
            fused.add(id(e2))
    return outs0


_FUSERS = {"matmul": _fuse_matmuls, "eltwise": _fuse_eltwise}


# ---------------------------------------------------------------------------
# the shared evaluator (eager interpreter == trace-time compiler)
# ---------------------------------------------------------------------------


def _dispatch_placed(ctx: LoweringContext, eqn, node, invals, cands,
                     cand_idx, env, fused, read, ready):
    """One placed equation through fusion (when candidates exist) else its
    per-kind rule. Factored out of :func:`eval_eqns` so the traced and the
    traced+instrumented paths share the dispatch logic exactly."""
    outs = None
    if cands is not None and node.kind in cands:
        peers = cands[node.kind][cand_idx[id(eqn)] + 1:]
        outs = _FUSERS[node.kind](ctx, eqn, peers, env, fused,
                                  read, ready, node, invals)
    if outs is None:
        outs = RULES[node.kind](ctx, eqn, node, invals)
    return outs


def eval_eqns(ctx: LoweringContext, eqns, env: dict) -> None:
    """Evaluate an equation run against ``env`` (var -> value), writing
    each equation's outputs back into ``env``. This is the inner loop of
    :func:`eval_placed` and the body of every per-partition stage program
    (``repro.mapper.compile.compile_partitioned`` slices one jaxpr's
    top-level equations into stages that each call this on their slice).

    With ``ctx.fuse`` the walk may evaluate a later placed equation
    *early*, fused into an earlier launch — only ever when all of its
    inputs were already computed, so dataflow (and numerics) are
    unchanged; its id lands in the ``fused`` set and its original slot is
    skipped.
    """

    def read(v):
        return v.val if isinstance(v, Literal) else env[v]

    def ready(e) -> bool:
        return all(isinstance(v, Literal) or v in env
                   for v in e.invars)

    # pre-filter fusion candidates per kind once: each lead then scans
    # only the later placed equations of its kind, not every equation
    cands: dict[str, list] | None = None
    cand_idx: dict[int, int] = {}
    if ctx.group and ctx.fuse:
        cands = {"matmul": [], "eltwise": []}
        for e in eqns:
            nd = ctx.node_by_eqn.get(id(e))
            if nd is not None and nd.kind in cands:
                lst = cands[nd.kind]
                cand_idx[id(e)] = len(lst)
                lst.append(e)

    fused: set[int] = set()
    for pos, eqn in enumerate(eqns):
        if id(eqn) in fused:
            continue
        invals = [read(v) for v in eqn.invars]
        name = eqn.primitive.name
        node = ctx.node_by_eqn.get(id(eqn))
        outs = None
        if name in CALL_PRIMS:
            inner = inner_jaxpr(eqn)
            if inner is not None and hasattr(inner, "jaxpr"):
                # inline only when placed nodes live inside; binding the
                # call otherwise preserves its custom differentiation rule
                if ctx.subtree_has_placed(inner.jaxpr):
                    outs = eval_placed(ctx, inner.jaxpr, inner.consts,
                                       invals)
            elif inner is not None and not inner.constvars:
                # remat2/checkpoint carry a raw (const-free) Jaxpr;
                # iter_eqns inlines it, so we must too or placed nodes
                # inside jax.checkpoint would silently bind
                if ctx.subtree_has_placed(inner):
                    outs = eval_placed(ctx, inner, [], invals)
        if outs is None and node is not None:
            tr = obs.tracer()
            if tr.enabled and not any(isinstance(x, jax.core.Tracer)
                                      for x in invals):
                # eager dispatch with tracing on: record the launch as an
                # execute-lane span, synced so dur covers the actual work
                # (drift joins these against the schedule's stage costs).
                # Never taken under jit tracing — operands are Tracers —
                # so compiled programs stay byte-identical.
                n0 = ctx.matmul_launches + ctx.eltwise_launches
                with tr.span(f"{node.kind}:{node.name}", lane="execute",
                             node=node.idx, kind=node.kind):
                    outs = _dispatch_placed(ctx, eqn, node, invals, cands,
                                            cand_idx, env, fused, read,
                                            ready)
                    if outs is not None:
                        jax.block_until_ready(outs)
                if outs is not None:
                    obs.metrics().counter("pim.kernel_launches").inc(
                        ctx.matmul_launches + ctx.eltwise_launches - n0)
            else:
                outs = _dispatch_placed(ctx, eqn, node, invals, cands,
                                        cand_idx, env, fused, read, ready)
        if outs is None:
            subfuns, bind_params = eqn.primitive.get_bind_params(eqn.params)
            ans = eqn.primitive.bind(*subfuns, *invals, **bind_params)
            outs = list(ans) if eqn.primitive.multiple_results else [ans]
        env.update(zip(eqn.outvars, outs, strict=True))


def eval_placed(ctx: LoweringContext, jaxpr, consts, args) -> list[Any]:
    """Evaluate ``jaxpr`` with placed equations rewritten via RULES.

    Works identically on concrete arrays (interpreter) and tracers
    (compiler): the only difference is who calls it and when.
    """
    env: dict[Any, Any] = {}
    env.update(zip(jaxpr.constvars, consts, strict=True))
    env.update(zip(jaxpr.invars, args, strict=True))
    eval_eqns(ctx, jaxpr.eqns, env)
    return [v.val if isinstance(v, Literal) else env[v]
            for v in jaxpr.outvars]
