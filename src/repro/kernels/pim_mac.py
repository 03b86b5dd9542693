"""PIM MAC / matmul as Pallas TPU kernels — the hardware adaptation of the
paper's compute unit (DESIGN.md §2, layer 3).

Mapping of the paper's structures onto TPU (this is an *adaptation*, not an
emulation — the PIM array's physics have no TPU analogue, its dataflow
does):

  paper (SOT-MRAM subarray)            TPU kernel
  -----------------------------------  ----------------------------------
  1024-column parallel MACs            VMEM lane dimension (8x128 tiles)
  operands stay in-array (no movement) operands stay in VMEM across the
                                       K-loop (BlockSpec reuse)
  ping-pong accumulator columns        f32 VMEM scratch accumulator that
                                       alternates role across grid steps
  455-cell intermediate writes (the    never spill partial products to
  FloatPIM flaw the paper fixes)       HBM — accumulate in scratch only
  all placed blocks compute in         leading *group* grid axis: one
  parallel across subarrays            launch covers every block of a
                                       placed node (or several fused
                                       nodes), not one launch per block

``pim_mac``            — elementwise fused multiply-add over tiles.
``pim_matmul``         — blocked matmul, grid (M/bm, N/bn, K/bk),
                         accumulating in VMEM scratch, writing the output
                         tile once on the last K step (K innermost =
                         sequential on TPU).
``pim_matmul_grouped`` — the same kernel with a leading group dimension:
                         ``(G, M, K) @ (G, K, N) -> (G, M, N)`` in ONE
                         ``pallas_call`` over grid (G, M/bm, N/bn, K/bk).
                         The G axis is the subarray-parallelism of the
                         paper made explicit: group g is the block
                         resident on subarray g, and all groups execute
                         under a single dispatch exactly as the SOT-MRAM
                         arrays compute all placed blocks concurrently.
``pim_mac_grouped``    — many independent (ragged) eltwise MACs fused
                         into one launch by flatten+concat, the shared
                         peripheral FP units serving a whole wave of
                         eltwise ops per dispatch.

Each ``pallas_call`` names itself after its entry point (``name=``:
``pim_mac``, ``pim_matmul``, ``pim_matmul_grouped``,
``pim_matmul_grouped_q``), so the compiled custom call — and the op a
profile shows — keeps that name whatever the kernel functions or their
callers are called.

All carry a ``custom_vjp`` whose backward passes are themselves PIM
kernel calls (dA = g @ B^T and dB = A^T @ g are in-array matmuls — and
for the grouped forms, *grouped* in-array matmuls, so ``jax.grad``
through a compiled schedule stays one-launch-per-node in the backward
too; the eltwise cotangents are in-array MACs) — the paper's training
claim is exactly that backprop stays in the array, and without the VJP
the compiled schedule path could not differentiate through
``pallas_call`` at all.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.mode import resolve_interpret


# ---------------------------------------------------------------------------
# elementwise MAC
# ---------------------------------------------------------------------------


def _mac_kernel(a_ref, b_ref, acc_ref, o_ref):
    o_ref[...] = acc_ref[...] + a_ref[...] * b_ref[...]


# rows of ``block`` lanes per grid step: a multiple of every dtype's
# sublane tile (8 f32, 16 bf16, 32 int8), so the block obeys the TPU's
# 8x128 rule; waves of at most this many rows take one full-extent step
_MAC_ROWS = 128


def _mac_call(a, b, acc, block: int, interpret: bool) -> jnp.ndarray:
    orig_shape = a.shape
    n = a.size
    rows = -(-n // block)
    tr = min(rows, _MAC_ROWS)
    rows = -(-rows // tr) * tr           # whole row tiles
    pad = rows * block - n
    aligned = not pad and a.ndim == 2 and a.shape[1] == block

    def prep(x):
        if aligned:
            return x                     # already (rows, block): no round-trip
        x = x.reshape(-1)
        if pad:
            x = jnp.pad(x, (0, pad))
        return x.reshape(-1, block)

    a2, b2, acc2 = prep(a), prep(b), prep(acc)
    out = pl.pallas_call(
        _mac_kernel,
        grid=(rows // tr,),
        in_specs=[pl.BlockSpec((tr, block), lambda i: (i, 0))] * 3,
        out_specs=pl.BlockSpec((tr, block), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, block), acc.dtype),
        interpret=interpret,
        name="pim_mac",
    )(a2, b2, acc2)
    if aligned:
        return out
    if pad:
        return out.reshape(-1)[:n].reshape(orig_shape)
    return out.reshape(orig_shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _pim_mac_vjp(a, b, acc, block, interpret):
    return _mac_call(a, b, acc, block, interpret)


def _pim_mac_fwd(a, b, acc, block, interpret):
    return _mac_call(a, b, acc, block, interpret), (a, b)


def _pim_mac_bwd(block, interpret, res, g):
    # out = acc + a*b: da = g*b and db = g*a are themselves in-array MACs
    # (accumulating into zero); dacc passes through.
    a, b = res
    zero = jnp.zeros_like(g)
    da = _pim_mac_vjp(g, b.astype(g.dtype), zero, block, interpret)
    db = _pim_mac_vjp(g, a.astype(g.dtype), zero, block, interpret)
    return da.astype(a.dtype), db.astype(b.dtype), g


_pim_mac_vjp.defvjp(_pim_mac_fwd, _pim_mac_bwd)


def pim_mac(a: jnp.ndarray, b: jnp.ndarray, acc: jnp.ndarray,
            *, block: int = 1024,
            interpret: bool | None = None) -> jnp.ndarray:
    """Elementwise acc + a*b, tiled along the last dim. Differentiable
    (custom VJP; cotangents are pim_mac calls)."""
    assert a.shape == b.shape == acc.shape
    return _pim_mac_vjp(a, b, acc, block, resolve_interpret(interpret))


def pim_mac_grouped(triples, *, block: int = 1024,
                    interpret: bool | None = None) -> list:
    """One kernel launch for a *wave* of independent eltwise MACs.

    ``triples`` is a sequence of same-dtype ``(a, b, acc)`` triples of
    arbitrary (ragged) shapes; each contributes ``acc + a*b``. Operands
    are flattened and concatenated so the whole wave rides a single
    ``pim_mac`` dispatch — the grouped counterpart of the peripheral FP
    units serving many eltwise ops in one array cycle. Returns the per-
    triple outputs in order, reshaped back. Differentiable end-to-end:
    the concat/split are native JAX, the MAC itself carries the custom
    VJP (whose cotangents are two more grouped launches).
    """
    triples = list(triples)
    interpret = resolve_interpret(interpret)
    assert triples, "pim_mac_grouped needs at least one (a, b, acc) triple"
    shapes = [a.shape for a, _, _ in triples]
    sizes = [a.size for a, _, _ in triples]
    if len(triples) == 1:
        a, b, acc = triples[0]
        return [pim_mac(a, b, acc, block=block, interpret=interpret)]
    fa = jnp.concatenate([a.reshape(-1) for a, _, _ in triples])
    fb = jnp.concatenate([b.reshape(-1) for _, b, _ in triples])
    facc = jnp.concatenate([acc.reshape(-1) for _, _, acc in triples])
    flat = pim_mac(fa, fb, facc, block=block, interpret=interpret)
    outs, off = [], 0
    for shape, size in zip(shapes, sizes):
        outs.append(jax.lax.dynamic_slice_in_dim(flat, off, size)
                    .reshape(shape))
        off += size
    return outs


# ---------------------------------------------------------------------------
# blocked matmul with scratch accumulation
# ---------------------------------------------------------------------------


def _matmul_kernel(a_ref, b_ref, o_ref, acc_ref, *, n_k: int):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(ik == n_k - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _matmul_call(a, b, bm: int, bn: int, bk: int,
                 interpret: bool) -> jnp.ndarray:
    m, k = a.shape
    k2, n = b.shape
    assert k == k2
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (m, n, k)
    n_k = k // bk
    return pl.pallas_call(
        functools.partial(_matmul_kernel, n_k=n_k),
        grid=(m // bm, n // bn, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="pim_matmul",
    )(a, b)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def _pim_matmul_vjp(a, b, bm, bn, bk, interpret):
    return _matmul_call(a, b, bm, bn, bk, interpret)


def _pim_matmul_fwd(a, b, bm, bn, bk, interpret):
    return _matmul_call(a, b, bm, bn, bk, interpret), (a, b)


def _pim_matmul_bwd(bm, bn, bk, interpret, res, g):
    # dA = g @ B^T and dB = A^T @ g: both stay in the array as blocked
    # matmuls. Tile-size bookkeeping: g is (m, n), so the grids below need
    # (bm, bk, bn) resp. (bk, bn, bm) to keep every axis divisible.
    a, b = res
    da = _pim_matmul_vjp(g, b.T, bm, bk, bn, interpret)
    db = _pim_matmul_vjp(a.T, g, bk, bn, bm, interpret)
    return da.astype(a.dtype), db.astype(b.dtype)


_pim_matmul_vjp.defvjp(_pim_matmul_fwd, _pim_matmul_bwd)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def pim_matmul(a: jnp.ndarray, b: jnp.ndarray, *, bm: int = 128,
               bn: int = 128, bk: int = 128,
               interpret: bool | None = None) -> jnp.ndarray:
    """f32 C = A @ B with (bm, bn, bk) VMEM tiles (MXU-aligned on TPU).
    Differentiable (custom VJP; both cotangents are pim_matmul calls)."""
    return _pim_matmul_vjp(a, b, bm, bn, bk, resolve_interpret(interpret))


# ---------------------------------------------------------------------------
# grouped blocked matmul: one launch for a whole stack of block operands
# ---------------------------------------------------------------------------


def _matmul_grouped_kernel(a_ref, b_ref, o_ref, acc_ref, *, n_k: int):
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[0], b_ref[0],
                            preferred_element_type=jnp.float32)

    @pl.when(ik == n_k - 1)
    def _done():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def _matmul_grouped_call(a, b, bm: int, bn: int, bk: int,
                         interpret: bool, col_groups: int) -> jnp.ndarray:
    ga, m, k = a.shape
    g, k2, n = b.shape
    assert g == ga * col_groups and k == k2, (a.shape, b.shape, col_groups)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (m, n, k)
    n_k = k // bk
    return pl.pallas_call(
        functools.partial(_matmul_grouped_kernel, n_k=n_k),
        grid=(g, m // bm, n // bn, n_k),
        in_specs=[
            # shared-A mode (col_groups > 1): group g reads A slab
            # g // col_groups through the index map — no materialized
            # replication of the activations across a node's col blocks
            pl.BlockSpec((1, bm, bk),
                         lambda gg, i, j, kk, cg=col_groups:
                         (gg // cg, i, kk)),
            pl.BlockSpec((1, bk, bn), lambda gg, i, j, kk: (gg, kk, j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda gg, i, j, kk: (gg, i, j)),
        out_shape=jax.ShapeDtypeStruct((g, m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="pim_matmul_grouped",
    )(a, b)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6))
def _pim_matmul_grouped_vjp(a, b, bm, bn, bk, interpret, col_groups):
    return _matmul_grouped_call(a, b, bm, bn, bk, interpret, col_groups)


def _pim_matmul_grouped_fwd(a, b, bm, bn, bk, interpret, col_groups):
    return (_matmul_grouped_call(a, b, bm, bn, bk, interpret, col_groups),
            (a, b))


def _pim_matmul_grouped_bwd(bm, bn, bk, interpret, col_groups, res, g):
    # dA_g = g_g @ B_g^T and dB_g = A_g^T @ g_g stay grouped — the
    # backward of one launch is one launch, per cotangent. Tile
    # bookkeeping mirrors the per-block VJP: g is (G, m, n), so the
    # grids need (bm, bk, bn) resp. (bk, bn, bm). With a shared A, dA
    # additionally segment-sums the per-col-group cotangents.
    a, b = res
    da = _pim_matmul_grouped_vjp(g, jnp.swapaxes(b, 1, 2), bm, bk, bn,
                                 interpret, 1)
    if col_groups > 1:
        da = da.reshape(a.shape[0], col_groups, *da.shape[1:]).sum(axis=1)
    db = _pim_matmul_grouped_vjp(jnp.swapaxes(a, 1, 2), g, bk, bn, bm,
                                 interpret, col_groups)
    return da.astype(a.dtype), db.astype(b.dtype)


_pim_matmul_grouped_vjp.defvjp(_pim_matmul_grouped_fwd,
                               _pim_matmul_grouped_bwd)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret",
                                             "col_groups"))
def pim_matmul_grouped(a: jnp.ndarray, b: jnp.ndarray, *, bm: int = 128,
                       bn: int = 128, bk: int = 128,
                       interpret: bool | None = None,
                       col_groups: int = 1) -> jnp.ndarray:
    """f32 ``C[g] = A[g // col_groups] @ B[g]`` for a stack of G = len(B)
    block operands in ONE ``pallas_call`` (grid ``(G, M/bm, N/bn,
    K/bk)``, per-group VMEM scratch accumulation over the K axis). Group
    g is a placed weight block resident on subarray g: the single launch
    mirrors the paper's subarrays computing all placed blocks in
    parallel, where the per-block ``pim_matmul`` paid one dispatch per
    block.

    ``col_groups`` is the shared-A mode: a placed node's ``col_groups``
    column blocks all consume the same activation row-chunk, so A holds
    one slab per *row* chunk (``G // col_groups`` slabs) and the kernel's
    index map fans it out — no materialized replication. Differentiable
    (custom VJP; both cotangents are grouped calls, dA segment-summed
    over the col groups when A is shared).

    Each group's K-axis accumulation order and tile shapes are identical
    to a standalone ``pim_matmul`` on the same padded operands, so
    grouped results are bit-identical to the per-block path."""
    return _pim_matmul_grouped_vjp(a, b, bm, bn, bk,
                                   resolve_interpret(interpret), col_groups)


# ---------------------------------------------------------------------------
# quantized grouped matmul: dequantize-on-load from n-bit stored weights
# ---------------------------------------------------------------------------


def _matmul_grouped_q_kernel(a_ref, q_ref, s_ref, o_ref, acc_ref, *,
                             n_k: int):
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # dequantize-on-load: the stored block holds grid codes q, the
    # per-(group, column) scale rides the peripheral register; the MAC
    # datapath sees q * s and accumulates in f32 as always.
    acc_ref[...] += jnp.dot(a_ref[0], q_ref[0] * s_ref[0],
                            preferred_element_type=jnp.float32)

    @pl.when(ik == n_k - 1)
    def _done():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def _matmul_grouped_q_call(a, q, s, bm: int, bn: int, bk: int,
                           interpret: bool, col_groups: int) -> jnp.ndarray:
    ga, m, k = a.shape
    g, k2, n = q.shape
    assert g == ga * col_groups and k == k2, (a.shape, q.shape, col_groups)
    assert s.shape == (g, 1, n), (s.shape, q.shape)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (m, n, k)
    n_k = k // bk
    return pl.pallas_call(
        functools.partial(_matmul_grouped_q_kernel, n_k=n_k),
        grid=(g, m // bm, n // bn, n_k),
        in_specs=[
            pl.BlockSpec((1, bm, bk),
                         lambda gg, i, j, kk, cg=col_groups:
                         (gg // cg, i, kk)),
            pl.BlockSpec((1, bk, bn), lambda gg, i, j, kk: (gg, kk, j)),
            # one scale row per group, tiled along N with the B block
            pl.BlockSpec((1, 1, bn), lambda gg, i, j, kk: (gg, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda gg, i, j, kk: (gg, i, j)),
        out_shape=jax.ShapeDtypeStruct((g, m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="pim_matmul_grouped_q",
    )(a, q, s)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _pim_matmul_grouped_q_vjp(a, q, s, bm, bn, bk, interpret, col_groups):
    return _matmul_grouped_q_call(a, q, s, bm, bn, bk, interpret,
                                  col_groups)


def _pim_matmul_grouped_q_fwd(a, q, s, bm, bn, bk, interpret, col_groups):
    return (_matmul_grouped_q_call(a, q, s, bm, bn, bk, interpret,
                                   col_groups),
            (a, q, s))


def _pim_matmul_grouped_q_bwd(bm, bn, bk, interpret, col_groups, res, g):
    # fp32-accumulating backward: dA runs against the *dequantized*
    # weights (q * s, formed once outside the launch), and the stored-code
    # cotangent is dq = (A^T g) * s — both grouped fp32 launches, so grad
    # flow keeps full precision and composes with quantize_ste's
    # straight-through dw = dq / s into exactly dW = A^T g. Scales are
    # placement constants: ds = 0.
    a, q, s = res
    b = q * s
    da = _pim_matmul_grouped_vjp(g, jnp.swapaxes(b, 1, 2), bm, bk, bn,
                                 interpret, 1)
    if col_groups > 1:
        da = da.reshape(a.shape[0], col_groups, *da.shape[1:]).sum(axis=1)
    dq = _pim_matmul_grouped_vjp(jnp.swapaxes(a, 1, 2), g, bk, bn, bm,
                                 interpret, col_groups) * s
    return da.astype(a.dtype), dq.astype(q.dtype), jnp.zeros_like(s)


_pim_matmul_grouped_q_vjp.defvjp(_pim_matmul_grouped_q_fwd,
                                 _pim_matmul_grouped_q_bwd)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret",
                                             "col_groups"))
def pim_matmul_grouped_q(a: jnp.ndarray, q: jnp.ndarray, s: jnp.ndarray, *,
                         bm: int = 128, bn: int = 128, bk: int = 128,
                         interpret: bool | None = None,
                         col_groups: int = 1) -> jnp.ndarray:
    """``pim_matmul_grouped`` over quantized stored weights:
    ``C[g] = A[g // col_groups] @ (Q[g] * S[g])`` in one launch.

    ``Q`` holds each placed block's on-grid weight values (f32-carried
    codes from ``core.quant.quantize_axis`` — int8 / fp8-style grids) and
    ``S`` is the per-(group, output-column) scale, shape ``(G, 1, N)``:
    the scale lives in the block's peripheral register and is applied on
    load inside the kernel, mirroring a subarray that stores ``n_bits``
    cells per weight and rescales on the shared column periphery.
    Per-tile math is ``dot(a, q * s)`` — elementwise dequantize then the
    same f32 accumulation order as ``pim_matmul_grouped`` on ``q * s``,
    so results are bit-identical to the per-block oracle running on
    pre-dequantized blocks. Differentiable: see
    ``_pim_matmul_grouped_q_bwd``."""
    return _pim_matmul_grouped_q_vjp(a, q, s, bm, bn, bk,
                                     resolve_interpret(interpret),
                                     col_groups)
