"""Causal GQA flash attention as a Pallas TPU kernel.

The paper's core efficiency insight — *never write intermediates out of the
array* (FloatPIM's 455-cell writes are its energy bottleneck) — maps onto
TPU attention as: never write the [S, S] score matrix to HBM. This kernel
keeps the online-softmax state (acc, m, l) in VMEM scratch across the KV
grid axis and writes only the [qc, D] output tile.

Grid: (B, H, S/qc, S/kc), KV innermost ("arbitrary" = sequential on TPU so
scratch carries). GQA is handled in the BlockSpec index map (kv head =
h // (H/G)) — no repeated-KV materialization. Fully-masked blocks
(kv block entirely in the causal future) are skipped with ``pl.when``.

Validated against ``ref.flash_attention_ref`` in interpret mode across a
shape/dtype sweep; ``repro.models.attention.flash_attention_xla`` is the
mathematically identical XLA fallback used on non-TPU backends.

``paged_decode_attention_grouped`` extends the same grouped-launch idea
to paged-KV decode serving: one ``pallas_call`` covers *every* batch
slot and every KV head, and streams each slot's KV pages straight out of
the shared block pool with hand-issued async copies addressed through a
scalar-prefetched block table (no materialized [B, W*bs, G, D] gather).
A page is one contiguous slab of all KV heads of ``bs`` keys, read as
``(bs * G, D)``, so one step copies ~128 keys' worth of pages, all heads
at once, and folds them into every head's online softmax in VMEM scratch
while the next chunk's copies are already in flight. Copies stop at each
slot's length, so the work tracks the keys actually cached, not the
table's width.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import quant
from repro.kernels.mode import resolve_interpret

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  qc: int, kc: int, n_k: int, scale: float):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # skip kv blocks strictly in the causal future of this q block
    @pl.when(ik * kc <= iq * qc + qc - 1)
    def _compute():
        q = q_ref[0, 0]                    # [qc, D]
        k = k_ref[0, 0]                    # [kc, D]
        v = v_ref[0, 0]
        sc = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        q_pos = iq * qc + jax.lax.broadcasted_iota(jnp.int32, (qc, kc), 0)
        k_pos = ik * kc + jax.lax.broadcasted_iota(jnp.int32, (qc, kc), 1)
        sc = jnp.where(q_pos >= k_pos, sc, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, sc.max(axis=-1, keepdims=True))
        p = jnp.exp(sc - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = (acc_ref[...] * alpha
                        + jnp.dot(p.astype(v.dtype), v,
                                  preferred_element_type=jnp.float32))
        m_ref[...] = m_new

    @pl.when(ik == n_k - 1)
    def _done():
        o_ref[0, 0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-20)).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("q_chunk", "kv_chunk", "interpret"))
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    q_chunk: int = 256, kv_chunk: int = 256,
                    interpret: bool | None = None) -> jnp.ndarray:
    """q: [B,S,H,D]; k/v: [B,S,G,D] -> [B,S,H,D] (causal)."""
    b, s, h, d = q.shape
    g = k.shape[2]
    rep = h // g
    qc = min(q_chunk, s)
    kc = min(kv_chunk, s)
    assert s % qc == 0 and s % kc == 0
    nq, nk = s // qc, s // kc
    scale = 1.0 / math.sqrt(d)

    # layout: [B,H,S,D] blocks; kv head via index map (GQA — no repeat)
    qt = jnp.moveaxis(q, 2, 1)            # [B,H,S,D]
    kt = jnp.moveaxis(k, 2, 1)            # [B,G,S,D]
    vt = jnp.moveaxis(v, 2, 1)

    out = pl.pallas_call(
        functools.partial(_flash_kernel, qc=qc, kc=kc, n_k=nk, scale=scale),
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, qc, d),
                         lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
            pl.BlockSpec((1, 1, kc, d),
                         lambda ib, ih, iq, ik: (ib, ih // rep, ik, 0)),
            pl.BlockSpec((1, 1, kc, d),
                         lambda ib, ih, iq, ik: (ib, ih // rep, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, qc, d),
                               lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((qc, d), jnp.float32),
            pltpu.VMEM((qc, 1), jnp.float32),
            pltpu.VMEM((qc, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(qt, kt, vt)
    return jnp.moveaxis(out, 1, 2)        # [B,S,H,D]


# ---------------------------------------------------------------------------
# grouped paged-KV decode attention (one launch for all batch slots)
# ---------------------------------------------------------------------------


def _paged_decode_kernel(tbl_ref, pos_ref, q_ref, k_hbm, v_hbm, o_ref,
                         k_buf, v_buf, sems, slot_ref, acc_ref, m_ref, l_ref,
                         *, bs: int, w: int, g: int, scale: float):
    b = pl.program_id(0)
    n_b = pl.num_programs(0)
    pages = k_buf.shape[1]
    h = q_ref.shape[1]

    def n_pages(s):
        # the pages that hold slot s's keys 0..pos; an idle lane, at
        # position 0, holds one
        return jnp.minimum(pos_ref[s] // bs + 1, w)

    def n_chunks(s):
        return (n_pages(s) + pages - 1) // pages

    def page_copies(s, c, slot, act):
        """Chunk ``c`` of slot ``s``: one copy of K and one of V for each
        of its pages that holds a key (``act`` starts or waits on each)."""
        n = n_pages(s)
        for j in range(pages):
            page = c * pages + j

            @pl.when(page < n)
            def _():
                blk = tbl_ref[s * w + page]
                for i, (hbm, buf) in enumerate(((k_hbm, k_buf),
                                                (v_hbm, v_buf))):
                    act(pltpu.make_async_copy(hbm.at[blk], buf.at[slot, j],
                                              sems.at[i, slot]))

    @pl.when(b == 0)
    def _():
        # pages past a slot's length are never copied: start V from
        # zeros, so that the value pass multiplies its zero weights by no
        # uninitialised (non-finite) rows (their scores are masked)
        v_buf[...] = jnp.zeros_like(v_buf)
        slot_ref[0] = 0
        page_copies(b, 0, 0, lambda cp: cp.start())

    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    p = pos_ref[b]
    q = q_ref[0]                                   # [H, D]
    rows = pages * bs * g                          # (key, kv head) rows
    # a chunk's rows are its keys' kv heads in pool order (key-major);
    # query head i reads kv head i // rep, and only its rows count
    col = jax.lax.broadcasted_iota(jnp.int32, (h, rows), 1)
    own = (col % g) == jax.lax.broadcasted_iota(jnp.int32, (h, rows),
                                                 0) // (h // g)

    def chunk(c, _):
        slot = slot_ref[0]
        last = c + 1 >= n_chunks(b)

        # the next chunk (this slot's, else the next slot's first) streams
        # into the other buffer while this one is folded in
        @pl.when(jnp.logical_or(~last, b + 1 < n_b))
        def _():
            page_copies(jnp.where(last, b + 1, b), jnp.where(last, 0, c + 1),
                        1 - slot, lambda cp: cp.start())

        page_copies(b, c, slot, lambda cp: cp.wait())
        slot_ref[0] = 1 - slot
        k = k_buf[slot].reshape(rows, -1)          # [keys * G, D]
        v = v_buf[slot].reshape(rows, -1)
        sc = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * scale
        key = c * pages * bs + col // g
        sc = jnp.where(own & (key <= p), sc, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, sc.max(axis=-1, keepdims=True))
        pr = jnp.exp(sc - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + pr.sum(axis=-1, keepdims=True)
        acc_ref[...] = (acc_ref[...] * alpha
                        + jnp.dot(pr.astype(v.dtype), v,
                                  preferred_element_type=jnp.float32))
        m_ref[...] = m_new

    jax.lax.fori_loop(0, n_chunks(b), chunk, None)
    o_ref[0] = (acc_ref[...]
                / jnp.maximum(l_ref[...], 1e-20)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention_grouped(q: jnp.ndarray, k_store: jnp.ndarray,
                                   v_store: jnp.ndarray,
                                   block_table: jnp.ndarray,
                                   pos: jnp.ndarray, *,
                                   interpret: bool | None = None
                                   ) -> jnp.ndarray:
    """Decode attention over a paged KV pool for all slots in one launch.

    q: [B, H, D] (one new token per slot); k/v_store: [N, bs, G, D] (the
    shared block pool, new token already scattered in); block_table:
    [B, W] int32 physical block ids (invalid entries clamped to the
    scratch block); pos: [B] int32 per-slot positions. Returns
    [B, H, D].

    Grid (B,), sequential: one step per slot, with an in-kernel loop over
    the slot's own chunks of ``P`` = 128 // bs pages (clipped to W), so a
    slot at position p costs ceil((p + 1) / bs / P) chunks, whatever W.
    The pools stay in HBM (``memory_space=ANY``); each chunk's pages are
    copied into VMEM by hand, addressed through the scalar-prefetched
    table, one async copy of K and one of V per page that holds a key and
    none past the slot's position, double-buffered: the next chunk (the
    slot's own, else the next slot's first) is in flight while this one
    is folded in. A trailing partial chunk copies only its pages and the
    position mask drops the rest.

    A page is read in the pool's own layout: ``[N, bs, G, D]`` viewed as
    ``[N, bs * G, D]`` (a bitcast when G is a multiple of the 8-row tile,
    so no relayout of the pool), one contiguous slab of every KV head of
    ``bs`` keys. All heads are attended at once: the scores of all H
    query heads against the chunk's ``keys * G`` (key, kv head) rows are
    one ``[H, keys * G]`` matmul, and a row of query head i keeps only
    the columns of kv head i // (H / G), so every kept score is the same
    f32 dot product as per head and every dropped one weighs exactly zero
    in the f32 online softmax and the bf16 value pass (one more matmul
    with the chunk's V rows). The online-softmax state (acc [H, D], m and
    l [H, 1]) lives in VMEM scratch.
    """
    b, h, d = q.shape
    n_blocks, bs, g, _ = k_store.shape
    w = block_table.shape[1]
    pages = max(1, min(w, 128 // bs))     # ~128 keys a chunk, <= the table
    row_spec = pl.BlockSpec((1, h, d), lambda ib, tbl, pos: (ib, 0, 0))
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    buf = pltpu.VMEM((2, pages, bs * g, d), k_store.dtype)

    return pl.pallas_call(
        functools.partial(_paged_decode_kernel, bs=bs, w=w, g=g,
                          scale=1.0 / math.sqrt(d)),
        name="paged_decode_attention",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b,),
            in_specs=[row_spec, any_spec, any_spec],
            out_specs=row_spec,
            scratch_shapes=[
                buf, buf,
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.VMEM((h, d), jnp.float32),
                pltpu.VMEM((h, 1), jnp.float32),
                pltpu.VMEM((h, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=resolve_interpret(interpret),
    )(block_table.astype(jnp.int32).reshape(-1), pos.astype(jnp.int32), q,
      k_store.reshape(n_blocks, bs * g, d),
      v_store.reshape(n_blocks, bs * g, d))


def _head_scale(scales: jnp.ndarray, g) -> jnp.ndarray:
    """Column ``g`` of a block's ``(bs, G)`` scales as ``(bs, 1)``: a
    masked sum, exact because every other term is zero, that avoids a
    dynamic lane index."""
    cols = jax.lax.broadcasted_iota(jnp.int32, scales.shape, 1)
    return jnp.sum(jnp.where(cols == g, scales, 0.0), axis=1, keepdims=True)


def _paged_decode_kernel_q(tbl_ref, pos_ref, q_ref, k_ref, ks_ref, v_ref,
                           vs_ref, o_ref, acc_ref, m_ref, l_ref, *, bs: int,
                           n_w: int, scale: float, kv_dtype: str):
    b = pl.program_id(0)
    g = pl.program_id(1)
    w = pl.program_id(2)

    @pl.when(w == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    p = pos_ref[b]

    @pl.when(w * bs <= p)
    def _compute():
        q = q_ref[0, 0]                    # [R, D]
        # dequantize on load: the streamed KV block is packed codes plus
        # one f32 scale per token — the same decode the XLA oracle path
        # runs, so grouped-vs-oracle stays bit-identical.
        k = quant.dequantize_kv(k_ref[0], _head_scale(ks_ref[0], g),
                                kv_dtype)
        v = quant.dequantize_kv(v_ref[0], _head_scale(vs_ref[0], g),
                                kv_dtype)
        sc = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        k_pos = w * bs + jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
        sc = jnp.where(k_pos <= p, sc, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, sc.max(axis=-1, keepdims=True))
        pr = jnp.exp(sc - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + pr.sum(axis=-1, keepdims=True)
        acc_ref[...] = (acc_ref[...] * alpha
                        + jnp.dot(pr.astype(v.dtype), v,
                                  preferred_element_type=jnp.float32))
        m_ref[...] = m_new

    @pl.when(w == n_w - 1)
    def _done():
        o_ref[0, 0] = (acc_ref[...]
                       / jnp.maximum(l_ref[...], 1e-20)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("kv_dtype", "interpret"))
def paged_decode_attention_grouped_q(q: jnp.ndarray, k_store: jnp.ndarray,
                                     k_scale: jnp.ndarray,
                                     v_store: jnp.ndarray,
                                     v_scale: jnp.ndarray,
                                     block_table: jnp.ndarray,
                                     pos: jnp.ndarray, *, kv_dtype: str,
                                     interpret: bool | None = None
                                     ) -> jnp.ndarray:
    """:func:`paged_decode_attention_grouped` over a *quantized* KV pool.

    k/v_store hold packed absmax-scaled codes ([N, bs, G, D] int8 /
    uint8 / uint16, see ``quant.quantize_kv``) and k/v_scale the
    per-(token, kv-head) f32 scales ([N, bs, G, 1]); both stream through
    the same scalar-prefetched block-table index maps, and the kernel
    dequantizes each block on load with f32 score/softmax accumulation —
    the activation-side mirror of ``pim_matmul_grouped_q``'s
    dequantize-on-load weight path. Codes stream as ``(bs, D)`` tiles of
    the ``[N, bs, G * D]`` view, as in the unquantized kernel; a block's
    scales stream for all G heads at once (``(bs, G)``, full extent) and
    the kernel picks its head's column.
    """
    b, h, d = q.shape
    n_blocks, bs, g, _ = k_store.shape
    w = block_table.shape[1]
    rep = h // g
    scale = 1.0 / math.sqrt(d)
    qg = q.reshape(b, g, rep, d)

    code_spec = pl.BlockSpec((1, bs, d), lambda ib, ig, iw, tbl, pos:
                             (tbl[ib, iw], 0, ig))
    scale_spec = pl.BlockSpec((1, bs, g), lambda ib, ig, iw, tbl, pos:
                              (tbl[ib, iw], 0, 0))
    out = pl.pallas_call(
        functools.partial(_paged_decode_kernel_q, bs=bs, n_w=w, scale=scale,
                          kv_dtype=quant.spec(kv_dtype).name),
        name="paged_decode_attention_q",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, g, w),
            in_specs=[
                pl.BlockSpec((1, 1, rep, d),
                             lambda ib, ig, iw, tbl, pos: (ib, ig, 0, 0)),
                code_spec,
                scale_spec,
                code_spec,
                scale_spec,
            ],
            out_specs=pl.BlockSpec((1, 1, rep, d),
                                   lambda ib, ig, iw, tbl, pos:
                                   (ib, ig, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((rep, d), jnp.float32),
                pltpu.VMEM((rep, 1), jnp.float32),
                pltpu.VMEM((rep, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, g, rep, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(block_table.astype(jnp.int32), pos.astype(jnp.int32), qg,
      k_store.reshape(n_blocks, bs, g * d), k_scale.reshape(n_blocks, bs, g),
      v_store.reshape(n_blocks, bs, g * d), v_scale.reshape(n_blocks, bs, g))
    return out.reshape(b, h, d)
