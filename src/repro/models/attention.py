"""GQA attention: full, flash (online-softmax, custom-VJP), and KV-cache
decode paths.

Memory design (what makes the 4k-train and 32k-prefill cells fit HBM):
  * **grouped einsums** — q is viewed as [B,S,G,R,D] (G = kv heads, R =
    q-per-kv); k/v are never materialized repeated. The G dim keeps the
    kv-head sharding end-to-end, so GSPMD never does the
    "involuntary full rematerialization" reshard that an explicit
    repeat+reshape triggers.
  * **flash_attention_xla** — online-softmax forward saving only (out, lse);
    the backward *recomputes* the score tiles per chunk (custom_vjp), the
    same strategy as the Pallas kernel in ``repro.kernels.flash_attention``
    (which is the TPU-native version of this exact math).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.core import quant
from repro.models import layers
from repro.parallel import sharding

NEG_INF = -1e30


def init_attention(key, d_model: int, n_heads: int, n_kv: int, head_dim: int,
                   dtype, *, qkv_bias: bool = False,
                   qk_norm: bool = False) -> dict:
    ks = jax.random.split(key, 4)
    p = {
        "wq": layers._dense_init(ks[0], (d_model, n_heads * head_dim), dtype),
        "wk": layers._dense_init(ks[1], (d_model, n_kv * head_dim), dtype),
        "wv": layers._dense_init(ks[2], (d_model, n_kv * head_dim), dtype),
        "wo": layers._dense_init(ks[3], (n_heads * head_dim, d_model), dtype),
    }
    if qkv_bias:
        p["q_bias"] = jnp.zeros((n_heads * head_dim,), dtype)
        p["k_bias"] = jnp.zeros((n_kv * head_dim,), dtype)
        p["v_bias"] = jnp.zeros((n_kv * head_dim,), dtype)
    if qk_norm:
        p["q_norm"] = jnp.ones((head_dim,), dtype)
        p["k_norm"] = jnp.ones((head_dim,), dtype)
    return p


def _project_qkv(x, params, cfg, positions):
    b, s, _ = x.shape
    hd = cfg.resolved_head_dim
    q = x @ params["wq"]
    k = x @ params["wk"]
    v = x @ params["wv"]
    if cfg.qkv_bias:
        q = q + params["q_bias"]
        k = k + params["k_bias"]
        v = v + params["v_bias"]
    q = q.reshape(b, s, cfg.n_heads, hd)
    k = k.reshape(b, s, cfg.n_kv_heads, hd)
    v = v.reshape(b, s, cfg.n_kv_heads, hd)
    if cfg.qk_norm:
        q = layers.head_rms_norm(q, params["q_norm"], cfg.norm_eps)
        k = layers.head_rms_norm(k, params["k_norm"], cfg.norm_eps)
    q = layers.apply_rope(q, positions, theta=cfg.rope_theta,
                          style=cfg.rope_style, sections=cfg.mrope_sections)
    k = layers.apply_rope(k, positions, theta=cfg.rope_theta,
                          style=cfg.rope_style, sections=cfg.mrope_sections)
    q = sharding.constrain(q, ("batch", None, "heads", None))
    k = sharding.constrain(k, ("batch", None, "kv_heads", None))
    v = sharding.constrain(v, ("batch", None, "kv_heads", None))
    return q, k, v


def _grouped(q, n_kv: int):
    """[B,S,H,D] -> [B,S,G,R,D] with G=n_kv (no data movement: H = G*R
    factorizes the existing 'heads' sharding into G-major)."""
    b, s, h, d = q.shape
    return q.reshape(b, s, n_kv, h // n_kv, d)


def full_causal_attention(q, k, v):
    """Reference full attention, grouped GQA einsums (short sequences,
    smoke tests, and the oracle for the flash paths)."""
    b, s, h, d = q.shape
    g = k.shape[2]
    qg = _grouped(q, g)
    scores = jnp.einsum("bqgrd,bkgd->bgrqk", qg, k) / math.sqrt(d)
    mask = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(mask, scores.astype(jnp.float32), NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bgrqk,bkgd->bqgrd", probs, v)
    return out.reshape(b, s, h, d)


# ---------------------------------------------------------------------------
# flash attention in pure XLA (chunked online softmax, custom VJP)
# ---------------------------------------------------------------------------
# Layout notes (hard-won against GSPMD):
#   * heads stay FLAT [B,S,H,D] and sharded over "heads" (model axis); k/v
#     are repeated to H per *chunk* (a ~64 MB transient), because constraining
#     the G=kv_heads dim pads it up to the mesh axis size (8 kv heads on a
#     16-way axis -> 2x memory on every q/score tensor);
#   * the causal mask is an additive (qc,kc) f32 penalty — a broadcast
#     `where` gets loop-hoisted by XLA into a [nq,nk,B,R,qc,kc] pred tensor
#     (~1 GiB at 4k);
#   * backward recomputes score tiles (custom_vjp), saving only (out, lse).

Q_CHUNK = 512
KV_CHUNK = 512


def _repeat_chunk(kc_blk, n_rep):
    """[B,kc,G,D] -> [B,kc,G*R,D] chunk-transient repeat."""
    if n_rep == 1:
        return kc_blk
    b, kc, g, d = kc_blk.shape
    rep = jnp.broadcast_to(kc_blk[:, :, :, None, :], (b, kc, g, n_rep, d))
    rep = rep.reshape(b, kc, g * n_rep, d)
    return sharding.constrain(rep, ("batch", None, "heads", None))


def _mask_penalty(qi, ki, iota_q, iota_k):
    causal = (qi * iota_q.shape[0] + iota_q)[:, None] >= (
        ki * iota_k.shape[0] + iota_k)[None]
    return jnp.where(causal, 0.0, NEG_INF).astype(jnp.float32)


def _flash_fwd_impl(q, k, v, q_chunk: int, kv_chunk: int):
    """q [B,S,H,D], k/v [B,S,G,D] -> (out [B,S,H,D], lse [B,H,S])."""
    b, s, h, d = q.shape
    g = k.shape[2]
    n_rep = h // g
    scale = 1.0 / math.sqrt(d)
    qc = min(q_chunk, s)
    kc = min(kv_chunk, s)
    nq, nk = s // qc, s // kc
    q = sharding.constrain(q, ("batch", None, "heads", None))
    iota_q = jnp.arange(qc)
    iota_k = jnp.arange(kc)
    kr = jnp.moveaxis(k.reshape(b, nk, kc, g, d), 1, 0)
    vr = jnp.moveaxis(v.reshape(b, nk, kc, g, d), 1, 0)

    def per_q(qi):
        qck = jax.lax.dynamic_slice_in_dim(q, qi * qc, qc, axis=1)

        def body(carry, inp):
            acc, m, l = carry
            kck, vck, ki = inp
            kck = _repeat_chunk(kck, n_rep)
            vck = _repeat_chunk(vck, n_rep)
            sc = (jnp.einsum("bqhd,bkhd->bhqk", qck, kck)
                  .astype(jnp.float32) * scale)
            sc = sc + _mask_penalty(qi, ki, iota_q, iota_k)[None, None]
            m_new = jnp.maximum(m, sc.max(axis=-1))
            p = jnp.exp(sc - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + p.sum(axis=-1)
            acc_new = (acc * alpha[..., None]
                       + jnp.einsum("bhqk,bkhd->bhqd",
                                    p.astype(qck.dtype), vck)
                       .astype(jnp.float32))
            return (acc_new, m_new, l_new), None

        acc0 = jnp.zeros((b, h, qc, d), jnp.float32)
        m0 = jnp.full((b, h, qc), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, h, qc), jnp.float32)
        (acc, m, l), _ = jax.lax.scan(body, (acc0, m0, l0),
                                      (kr, vr, jnp.arange(nk)))
        out_c = acc / jnp.maximum(l[..., None], 1e-20)
        lse_c = m + jnp.log(jnp.maximum(l, 1e-20))
        return jnp.moveaxis(out_c, 2, 1).astype(q.dtype), lse_c

    outs, lses = jax.lax.map(per_q, jnp.arange(nq))
    out = jnp.moveaxis(outs, 0, 1).reshape(b, s, h, d)
    lse = jnp.concatenate(jnp.unstack(lses, axis=0), axis=-1)  # [B,H,S]
    return out, lse


def _flash_bwd_impl(q, k, v, out, lse, dout, q_chunk: int, kv_chunk: int):
    b, s, h, d = q.shape
    g = k.shape[2]
    n_rep = h // g
    scale = 1.0 / math.sqrt(d)
    qc = min(q_chunk, s)
    kc = min(kv_chunk, s)
    nq, nk = s // qc, s // kc
    q = sharding.constrain(q, ("batch", None, "heads", None))
    dout = sharding.constrain(dout, ("batch", None, "heads", None))
    iota_q = jnp.arange(qc)
    iota_k = jnp.arange(kc)
    # bf16 inputs, f32 accumulation — explicit .astype would materialize
    # two full [B,S,H,D] f32 copies (~1 GiB each at 4k)
    delta = jnp.einsum("bshd,bshd->bhs", dout, out,
                       preferred_element_type=jnp.float32)

    def per_q(carry, qi):
        dk_acc, dv_acc = carry
        qck = jax.lax.dynamic_slice_in_dim(q, qi * qc, qc, axis=1)
        do_c = jax.lax.dynamic_slice_in_dim(dout, qi * qc, qc, axis=1)
        lse_c = jax.lax.dynamic_slice_in_dim(lse, qi * qc, qc, axis=-1)
        dl_c = jax.lax.dynamic_slice_in_dim(delta, qi * qc, qc, axis=-1)

        def body(carry2, inp):
            dq_acc, dk_a, dv_a = carry2
            kck, vck, ki = inp
            kck_r = _repeat_chunk(kck, n_rep)
            vck_r = _repeat_chunk(vck, n_rep)
            sc = (jnp.einsum("bqhd,bkhd->bhqk", qck, kck_r)
                  .astype(jnp.float32) * scale)
            sc = sc + _mask_penalty(qi, ki, iota_q, iota_k)[None, None]
            p = jnp.exp(sc - lse_c[..., None])            # [B,H,qc,kc]
            dv_blk = jnp.einsum("bhqk,bqhd->bkhd", p,
                                do_c.astype(jnp.float32))
            dp = jnp.einsum("bqhd,bkhd->bhqk", do_c, vck_r).astype(
                jnp.float32)
            ds = p * (dp - dl_c[..., None]) * scale
            dq_blk = jnp.einsum("bhqk,bkhd->bqhd", ds,
                                kck_r.astype(jnp.float32))
            dk_blk = jnp.einsum("bhqk,bqhd->bkhd", ds,
                                qck.astype(jnp.float32))
            # fold the repeated-head grads back to G kv heads
            dk_blk = dk_blk.reshape(b, kc, g, n_rep, d).sum(axis=3)
            dv_blk = dv_blk.reshape(b, kc, g, n_rep, d).sum(axis=3)
            dk_a = jax.lax.dynamic_update_slice_in_dim(
                dk_a, (jax.lax.dynamic_slice_in_dim(dk_a, ki * kc, kc, 1)
                       + dk_blk), ki * kc, axis=1)
            dv_a = jax.lax.dynamic_update_slice_in_dim(
                dv_a, (jax.lax.dynamic_slice_in_dim(dv_a, ki * kc, kc, 1)
                       + dv_blk), ki * kc, axis=1)
            return (dq_acc + dq_blk, dk_a, dv_a), None

        dq0 = jnp.zeros((b, qc, h, d), jnp.float32)
        (dq_c, dk_acc, dv_acc), _ = jax.lax.scan(
            body, (dq0, dk_acc, dv_acc), (jnp.moveaxis(
                k.reshape(b, nk, kc, g, d), 1, 0), jnp.moveaxis(
                    v.reshape(b, nk, kc, g, d), 1, 0), jnp.arange(nk)))
        # stack bf16, not f32 (the stacked dq is a full [B,S,H,D] buffer)
        return (dk_acc, dv_acc), dq_c.astype(q.dtype)

    dk0 = jnp.zeros((b, s, g, d), jnp.float32)
    dv0 = jnp.zeros((b, s, g, d), jnp.float32)
    (dk, dv), dqs = jax.lax.scan(per_q, (dk0, dv0), jnp.arange(nq))
    dq = jnp.moveaxis(dqs, 0, 1).reshape(b, s, h, d)
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_attention_xla(q, k, v, q_chunk: int = Q_CHUNK,
                        kv_chunk: int = KV_CHUNK):
    """q: [B,S,H,D]; k/v: [B,S,G,D] -> out [B,S,H,D]."""
    out, _ = _flash_fwd_impl(q, k, v, q_chunk, kv_chunk)
    return out


def _flash_fwd(q, k, v, q_chunk, kv_chunk):
    out, lse = _flash_fwd_impl(q, k, v, q_chunk, kv_chunk)
    return out, (q, k, v, out, lse)


def _flash_bwd(q_chunk, kv_chunk, res, dout):
    q, k, v, out, lse = res
    return _flash_bwd_impl(q, k, v, out, lse, dout, q_chunk, kv_chunk)


flash_attention_xla.defvjp(_flash_fwd, _flash_bwd)


def chunked_causal_attention(q, k, v, *, q_chunk: int = Q_CHUNK,
                             kv_chunk: int = KV_CHUNK):
    """[B,S,H,D] API over the flash path (memory: O(S * chunk))."""
    b, s, h, d = q.shape
    if USE_PAIR_SCAN:
        return flash_attention_pair(q, k, v, min(q_chunk, s))
    return flash_attention_xla(q, k, v, min(q_chunk, s), min(kv_chunk, s))


def attention_block(x, params, cfg, positions, *, chunked: bool):
    q, k, v = _project_qkv(x, params, cfg, positions)
    if chunked:
        out = chunked_causal_attention(q, k, v)
    else:
        out = full_causal_attention(q, k, v)
    b, s, h, d = out.shape
    out = out.reshape(b, s, h * d)
    return out @ params["wo"]


# ---------------------------------------------------------------------------
# decode path (one new token against a KV cache)
# ---------------------------------------------------------------------------


def init_kv_cache(batch: int, max_len: int, n_kv: int, head_dim: int, dtype):
    return {
        "k": jnp.zeros((batch, max_len, n_kv, head_dim), dtype),
        "v": jnp.zeros((batch, max_len, n_kv, head_dim), dtype),
    }


def decode_attention(x, params, cfg, cache: dict, pos: jnp.ndarray):
    """x: [B, 1, D]; cache holds max_len KV; pos: scalar current length.

    Returns (out [B, 1, D], updated cache). Grouped einsums — no repeated-KV
    materialization (at a 500k-token cache that repeat would be fatal).
    """
    b = x.shape[0]
    hd = cfg.resolved_head_dim
    if cfg.rope_style == "mrope":
        positions = jnp.broadcast_to(pos, (3, b, 1))
    else:
        positions = jnp.broadcast_to(pos, (b, 1))
    q, k_new, v_new = _project_qkv(x, params, cfg, positions)
    k = jax.lax.dynamic_update_slice_in_dim(cache["k"],
                                            k_new.astype(cache["k"].dtype),
                                            pos, axis=1)
    v = jax.lax.dynamic_update_slice_in_dim(cache["v"],
                                            v_new.astype(cache["v"].dtype),
                                            pos, axis=1)
    g = cfg.n_kv_heads
    qg = _grouped(q, g)                                    # [B,1,G,R,D]
    scores = (jnp.einsum("bqgrd,bkgd->bgrqk", qg, k).astype(jnp.float32)
              / math.sqrt(hd))
    valid = jnp.arange(k.shape[1])[None, None, None, None, :] <= pos
    scores = jnp.where(valid, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bgrqk,bkgd->bqgrd", probs, v)
    out = out.reshape(b, 1, cfg.n_heads * hd) @ params["wo"]
    return out, {"k": k, "v": v}


# ---------------------------------------------------------------------------
# paged decode path (block-table KV, per-slot positions)
# ---------------------------------------------------------------------------


def init_paged_kv_cache(num_blocks: int, block_size: int, n_kv: int,
                        head_dim: int, dtype, kv_dtype: str = "fp32"):
    """One attention site's share of the paged KV pool: position ``p`` of a
    slot lives at ``[table[p // block_size], p % block_size]``.

    ``kv_dtype`` other than fp32 stores packed absmax-scaled codes
    (``quant.quantize_kv``) with one f32 scale per (token, kv-head)
    vector riding in ``k_scale`` / ``v_scale`` leaves. Scales keep the
    block axis at position 1, so every allocator device op (CoW copy,
    swap, prefix export/import) round-trips codes+scales together."""
    shape = (num_blocks, block_size, n_kv, head_dim)
    if quant.spec(kv_dtype).name == "fp32":
        return {
            "k": jnp.zeros(shape, dtype),
            "v": jnp.zeros(shape, dtype),
        }
    ct = quant.code_dtype(kv_dtype)
    sshape = (num_blocks, block_size, n_kv, 1)
    return {
        "k": jnp.zeros(shape, ct),
        "k_scale": jnp.zeros(sshape, jnp.float32),
        "v": jnp.zeros(shape, ct),
        "v_scale": jnp.zeros(sshape, jnp.float32),
    }


def paged_decode_attention(x, params, cfg, cache: dict,
                           block_table: jnp.ndarray, pos: jnp.ndarray, *,
                           use_kernel: bool = False,
                           kv_dtype: str = "fp32"):
    """x: [B, 1, D]; cache k/v: [num_blocks, block_size, G, hd];
    block_table: [B, W] physical block per logical block (invalid entries
    clamped to the scratch block); pos: [B] per-slot current length.

    Returns (out [B, 1, D], updated cache). The new token's K/V scatter
    into each slot's tail block; the score pass gathers the slot's blocks
    through its table — per-slot positions, so mixed-progress slots (and
    recycled slots restarting at position 0) are exact in one batched
    call. Validity comes from the per-slot position bound, exactly like
    the contiguous path's mask.

    ``use_kernel=True`` routes the gather + score + softmax + value pass
    through ``repro.kernels.paged_decode_attention_grouped`` — one Pallas
    launch for every slot, each slot's KV pages copied through the
    scalar-prefetched block table up to its position instead of a
    materialized ``[B, W*bs, G, hd]`` XLA gather. The XLA path below
    stays the numerics oracle.

    ``kv_dtype`` other than fp32 quantizes the new token's K/V on
    scatter (codes + per-(token, head) scales, see
    ``init_paged_kv_cache``) and dequantizes on gather; scores and
    softmax accumulate in f32 either way. fp32 is the untouched
    original path, bit-identical storage included.
    """
    b = x.shape[0]
    hd = cfg.resolved_head_dim
    bs = cache["k"].shape[1]
    w = block_table.shape[1]
    quantized = quant.spec(kv_dtype).name != "fp32"
    if cfg.rope_style == "mrope":
        positions = jnp.broadcast_to(pos[None, :, None], (3, b, 1))
    else:
        positions = pos[:, None]
    q, k_new, v_new = _project_qkv(x, params, cfg, positions)
    blk = block_table[jnp.arange(b), pos // bs]            # [B] tail blocks
    off = pos % bs
    if quantized:
        k_codes, k_sc = quant.quantize_kv(k_new[:, 0], kv_dtype)
        v_codes, v_sc = quant.quantize_kv(v_new[:, 0], kv_dtype)
        new_cache = {
            "k": cache["k"].at[blk, off].set(
                k_codes.astype(cache["k"].dtype)),
            "k_scale": cache["k_scale"].at[blk, off].set(k_sc),
            "v": cache["v"].at[blk, off].set(
                v_codes.astype(cache["v"].dtype)),
            "v_scale": cache["v_scale"].at[blk, off].set(v_sc),
        }
    else:
        new_cache = {
            "k": cache["k"].at[blk, off].set(
                k_new[:, 0].astype(cache["k"].dtype)),
            "v": cache["v"].at[blk, off].set(
                v_new[:, 0].astype(cache["v"].dtype)),
        }
    k_store, v_store = new_cache["k"], new_cache["v"]
    if use_kernel:
        from repro.kernels.flash_attention import (
            paged_decode_attention_grouped,
            paged_decode_attention_grouped_q)
        if quantized:
            att = paged_decode_attention_grouped_q(
                q[:, 0], k_store, new_cache["k_scale"],
                v_store, new_cache["v_scale"], block_table, pos,
                kv_dtype=quant.spec(kv_dtype).name)
        else:
            att = paged_decode_attention_grouped(q[:, 0], k_store, v_store,
                                                 block_table, pos)
        out = att.reshape(b, 1, cfg.n_heads * hd) @ params["wo"]
        return out, new_cache
    if quantized:
        k = quant.dequantize_kv(k_store[block_table],
                                new_cache["k_scale"][block_table], kv_dtype)
        v = quant.dequantize_kv(v_store[block_table],
                                new_cache["v_scale"][block_table], kv_dtype)
        k = k.reshape(b, w * bs, cfg.n_kv_heads, hd)
        v = v.reshape(b, w * bs, cfg.n_kv_heads, hd)
    else:
        k = k_store[block_table].reshape(b, w * bs, cfg.n_kv_heads, hd)
        v = v_store[block_table].reshape(b, w * bs, cfg.n_kv_heads, hd)
    g = cfg.n_kv_heads
    qg = _grouped(q, g)                                    # [B,1,G,R,D]
    scores = (jnp.einsum("bqgrd,bkgd->bgrqk", qg, k).astype(jnp.float32)
              / math.sqrt(hd))
    valid = jnp.arange(w * bs)[None] <= pos[:, None]       # [B, L] per slot
    scores = jnp.where(valid[:, None, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bgrqk,bkgd->bqgrd", probs, v)
    out = out.reshape(b, 1, cfg.n_heads * hd) @ params["wo"]
    return out, new_cache


def paged_prefill_attention(x, params, cfg, cache: dict,
                            table_row: jnp.ndarray, p0: jnp.ndarray,
                            n_new: jnp.ndarray, *, kv_dtype: str = "fp32"):
    """Whole-prompt attention for one slot over the paged pool.

    x: [1, T, D] — T new prompt tokens (padded; entries past ``n_new``
    are don't-cares) occupying global positions ``p0 .. p0+n_new-1``;
    ``table_row``: [W] the slot's physical block ids; ``p0`` the first
    uncached position (block-aligned by construction: the engine admits
    on whole cached prefix blocks). Returns (att [1, T, D], updated
    cache).

    The new tokens' K/V scatter into the slot's blocks in one shot
    (padded tail entries land in the pinned scratch block); queries
    attend causally over the cached prefix *and* the new tokens through
    the same table gather the decode path uses, so the written KV — and
    every downstream decode — is mathematically identical to replaying
    the prompt token by token.
    """
    t = x.shape[1]
    hd = cfg.resolved_head_dim
    bs = cache["k"].shape[1]
    w = table_row.shape[0]
    quantized = quant.spec(kv_dtype).name != "fp32"
    gpos = p0 + jnp.arange(t)                              # [T] global pos
    if cfg.rope_style == "mrope":
        positions = jnp.broadcast_to(gpos[None, None], (3, 1, t))
    else:
        positions = gpos[None]
    q, k_new, v_new = _project_qkv(x, params, cfg, positions)
    new_valid = jnp.arange(t) < n_new
    # padded writes clamp to the scratch block (block 0): shape-static
    # scatter, garbage never lands in live blocks
    blk = jnp.where(new_valid, table_row[jnp.clip(gpos // bs, 0, w - 1)], 0)
    off = jnp.where(new_valid, gpos % bs, 0)
    if quantized:
        k_codes, k_sc = quant.quantize_kv(k_new[0], kv_dtype)
        v_codes, v_sc = quant.quantize_kv(v_new[0], kv_dtype)
        new_cache = {
            "k": cache["k"].at[blk, off].set(
                k_codes.astype(cache["k"].dtype)),
            "k_scale": cache["k_scale"].at[blk, off].set(k_sc),
            "v": cache["v"].at[blk, off].set(
                v_codes.astype(cache["v"].dtype)),
            "v_scale": cache["v_scale"].at[blk, off].set(v_sc),
        }
        k = quant.dequantize_kv(new_cache["k"][table_row],
                                new_cache["k_scale"][table_row], kv_dtype)
        v = quant.dequantize_kv(new_cache["v"][table_row],
                                new_cache["v_scale"][table_row], kv_dtype)
    else:
        new_cache = {
            "k": cache["k"].at[blk, off].set(
                k_new[0].astype(cache["k"].dtype)),
            "v": cache["v"].at[blk, off].set(
                v_new[0].astype(cache["v"].dtype)),
        }
        k, v = new_cache["k"][table_row], new_cache["v"][table_row]
    g = cfg.n_kv_heads
    k = k.reshape(1, w * bs, g, hd)
    v = v.reshape(1, w * bs, g, hd)
    qg = _grouped(q, g)                                    # [1,T,G,R,D]
    scores = (jnp.einsum("bqgrd,bkgd->bgrqk", qg, k).astype(jnp.float32)
              / math.sqrt(hd))
    # causal over global positions; keys beyond the written region are
    # excluded by the same bound
    valid = jnp.arange(w * bs)[None] <= gpos[:, None]      # [T, L]
    scores = jnp.where(valid[None, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bgrqk,bkgd->bqgrd", probs, v)
    out = out.reshape(1, t, cfg.n_heads * hd) @ params["wo"]
    return out, new_cache


def paged_kv_dequant_error(store: dict, ref: dict,
                           kv_dtype: str) -> jnp.ndarray:
    """Measured KV dequantization error of a quantized paged store
    against its fp32 golden twin: max over written entries of
    ``|dequant(codes, scale) - ref| / per-(token, head) absmax`` —
    directly comparable to ``quant.layer_error_budget(kv_dtype)``.

    Leaves are the transformer's stacked
    ``[n_units, num_blocks, block_size, G, head_dim]``; returns one
    scalar per unit (``[n_units]`` f32, zeros for fp32 stores).
    Unwritten entries are zero in both stores and contribute 0."""
    s = quant.spec(kv_dtype)
    errs = []
    for name in ("k", "v"):
        refv = jnp.asarray(ref[name], jnp.float32)
        if s.name == "fp32":
            dq = jnp.asarray(store[name], jnp.float32)
        else:
            dq = quant.dequantize_kv(store[name], store[name + "_scale"], s)
        amax = jnp.max(jnp.abs(refv), axis=-1, keepdims=True)
        rel = jnp.abs(dq - refv) / jnp.maximum(amax, 1e-20)
        errs.append(jnp.max(rel, axis=tuple(range(1, refv.ndim))))
    return jnp.maximum(errs[0], errs[1])


# ---------------------------------------------------------------------------
# pair-scan causal flash: zero wasted blocks (hillclimb, EXPERIMENTS §Perf)
# ---------------------------------------------------------------------------
# The rectangular fwd/bwd above scans ALL nq x nk chunk pairs and masks the
# strictly-future ones — at nq=nk=n that wastes (n-1)/2n of attention FLOPs
# (~44% at n=8). Here the scan runs over the n(n+1)/2 *valid* pairs only
# (static shapes: the lower-triangle pair list is precomputed), carrying the
# full online-softmax state for every q chunk and scatter-updating the one
# belonging to the current pair. Same math — validated against
# full_causal_attention in tests/test_attention_ssm.py.


def _pair_indices(n: int):
    qs, ks = [], []
    for qi in range(n):
        for ki in range(qi + 1):
            qs.append(qi)
            ks.append(ki)
    return jnp.asarray(qs, jnp.int32), jnp.asarray(ks, jnp.int32)


def _flash_fwd_pair_impl(q, k, v, chunk: int):
    b, s, h, d = q.shape
    g = k.shape[2]
    n_rep = h // g
    scale = 1.0 / math.sqrt(d)
    c = min(chunk, s)
    n = s // c
    q = sharding.constrain(q, ("batch", None, "heads", None))
    qi_idx, ki_idx = _pair_indices(n)
    iota = jnp.arange(c)
    diag_pen = jnp.where(iota[:, None] >= iota[None, :], 0.0,
                         NEG_INF).astype(jnp.float32)

    def body(carry, inp):
        acc, m, l = carry                  # [n,B,H,c,D], [n,B,H,c], ...
        qi, ki = inp
        qck = jax.lax.dynamic_slice_in_dim(q, qi * c, c, axis=1)
        kck = _repeat_chunk(
            jax.lax.dynamic_slice_in_dim(k, ki * c, c, axis=1), n_rep)
        vck = _repeat_chunk(
            jax.lax.dynamic_slice_in_dim(v, ki * c, c, axis=1), n_rep)
        sc = (jnp.einsum("bqhd,bkhd->bhqk", qck, kck)
              .astype(jnp.float32) * scale)
        sc = sc + jnp.where(qi == ki, 1.0, 0.0) * diag_pen[None, None]
        m_prev = jax.lax.dynamic_index_in_dim(m, qi, 0)      # [1,B,H,c]
        l_prev = jax.lax.dynamic_index_in_dim(l, qi, 0)
        a_prev = jax.lax.dynamic_index_in_dim(acc, qi, 0)
        m_new = jnp.maximum(m_prev[0], sc.max(axis=-1))
        p = jnp.exp(sc - m_new[..., None])
        alpha = jnp.exp(m_prev[0] - m_new)
        l_new = l_prev[0] * alpha + p.sum(axis=-1)
        a_new = (a_prev[0] * alpha[..., None]
                 + jnp.einsum("bhqk,bkhd->bhqd", p.astype(q.dtype), vck)
                 .astype(jnp.float32))
        acc = jax.lax.dynamic_update_index_in_dim(acc, a_new, qi, 0)
        m = jax.lax.dynamic_update_index_in_dim(m, m_new, qi, 0)
        l = jax.lax.dynamic_update_index_in_dim(l, l_new, qi, 0)
        return (acc, m, l), None

    acc0 = jnp.zeros((n, b, h, c, d), jnp.float32)
    m0 = jnp.full((n, b, h, c), NEG_INF, jnp.float32)
    l0 = jnp.zeros((n, b, h, c), jnp.float32)
    (acc, m, l), _ = jax.lax.scan(jax.checkpoint(body), (acc0, m0, l0),
                                  (qi_idx, ki_idx))
    out = acc / jnp.maximum(l[..., None], 1e-20)           # [n,B,H,c,D]
    out = out.transpose(1, 0, 3, 2, 4).reshape(b, s, h, d).astype(q.dtype)
    lse = (m + jnp.log(jnp.maximum(l, 1e-20)))             # [n,B,H,c]
    lse = lse.transpose(1, 2, 0, 3).reshape(b, h, s)
    return out, lse


def _flash_bwd_pair_impl(q, k, v, out, lse, dout, chunk: int):
    b, s, h, d = q.shape
    g = k.shape[2]
    n_rep = h // g
    scale = 1.0 / math.sqrt(d)
    c = min(chunk, s)
    n = s // c
    q = sharding.constrain(q, ("batch", None, "heads", None))
    dout = sharding.constrain(dout, ("batch", None, "heads", None))
    qi_idx, ki_idx = _pair_indices(n)
    iota = jnp.arange(c)
    diag_pen = jnp.where(iota[:, None] >= iota[None, :], 0.0,
                         NEG_INF).astype(jnp.float32)
    delta = jnp.einsum("bshd,bshd->bhs", dout, out,
                       preferred_element_type=jnp.float32)

    def body(carry, inp):
        dq, dk, dv = carry
        qi, ki = inp
        qck = jax.lax.dynamic_slice_in_dim(q, qi * c, c, axis=1)
        do_c = jax.lax.dynamic_slice_in_dim(dout, qi * c, c, axis=1)
        lse_c = jax.lax.dynamic_slice_in_dim(lse, qi * c, c, axis=-1)
        dl_c = jax.lax.dynamic_slice_in_dim(delta, qi * c, c, axis=-1)
        kck_r = _repeat_chunk(
            jax.lax.dynamic_slice_in_dim(k, ki * c, c, axis=1), n_rep)
        vck_r = _repeat_chunk(
            jax.lax.dynamic_slice_in_dim(v, ki * c, c, axis=1), n_rep)
        sc = (jnp.einsum("bqhd,bkhd->bhqk", qck, kck_r)
              .astype(jnp.float32) * scale)
        sc = sc + jnp.where(qi == ki, 1.0, 0.0) * diag_pen[None, None]
        p = jnp.exp(sc - lse_c[..., None])
        dv_blk = jnp.einsum("bhqk,bqhd->bkhd", p, do_c.astype(jnp.float32))
        dp = jnp.einsum("bqhd,bkhd->bhqk", do_c, vck_r).astype(jnp.float32)
        ds = p * (dp - dl_c[..., None]) * scale
        dq_blk = jnp.einsum("bhqk,bkhd->bqhd", ds,
                            kck_r.astype(jnp.float32)).astype(q.dtype)
        dk_blk = jnp.einsum("bhqk,bqhd->bkhd", ds, qck.astype(jnp.float32))
        dk_blk = dk_blk.reshape(b, c, g, n_rep, d).sum(axis=3)
        dv_blk = dv_blk.reshape(b, c, g, n_rep, d).sum(axis=3)
        dq = jax.lax.dynamic_update_slice_in_dim(
            dq, jax.lax.dynamic_slice_in_dim(dq, qi * c, c, 1) + dq_blk,
            qi * c, axis=1)
        dk = jax.lax.dynamic_update_slice_in_dim(
            dk, jax.lax.dynamic_slice_in_dim(dk, ki * c, c, 1)
            + dk_blk.astype(k.dtype), ki * c, axis=1)
        dv = jax.lax.dynamic_update_slice_in_dim(
            dv, jax.lax.dynamic_slice_in_dim(dv, ki * c, c, 1)
            + dv_blk.astype(v.dtype), ki * c, axis=1)
        return (dq, dk, dv), None

    dq0 = jnp.zeros(q.shape, q.dtype)
    dk0 = jnp.zeros(k.shape, k.dtype)
    dv0 = jnp.zeros(v.shape, v.dtype)
    (dq, dk, dv), _ = jax.lax.scan(jax.checkpoint(body), (dq0, dk0, dv0),
                                   (qi_idx, ki_idx))
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def flash_attention_pair(q, k, v, chunk: int = 512):
    out, _ = _flash_fwd_pair_impl(q, k, v, chunk)
    return out


def _fp_fwd(q, k, v, chunk):
    out, lse = _flash_fwd_pair_impl(q, k, v, chunk)
    return out, (q, k, v, out, lse)


def _fp_bwd(chunk, res, dout):
    q, k, v, out, lse = res
    return _flash_bwd_pair_impl(q, k, v, out, lse, dout, chunk)


flash_attention_pair.defvjp(_fp_fwd, _fp_bwd)

# default the model path to the pair-scan variant (hillclimb result);
# the rectangular variant stays for ablation.
USE_PAIR_SCAN = True
