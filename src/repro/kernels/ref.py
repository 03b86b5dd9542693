"""Pure-jnp oracles for every Pallas kernel (the correctness contract).

Each kernel in this package is validated against these references across a
shape/dtype sweep in ``tests/test_kernels.py`` (interpret mode on CPU; the
BlockSpec tiling targets TPU VMEM).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def pim_mac_ref(a: jnp.ndarray, b: jnp.ndarray,
                acc: jnp.ndarray) -> jnp.ndarray:
    """Elementwise FP32 MAC — same semantics the PIM subarray computes
    (IEEE-754 f32; bit-exactness of the PIM procedure itself is proven
    against XLA ops in tests/test_fp_bitexact.py)."""
    return acc + a * b


def pim_matmul_ref(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """f32 matmul oracle for the PIM-tiled matmul kernel."""
    return jnp.dot(a.astype(jnp.float32), b.astype(jnp.float32),
                   preferred_element_type=jnp.float32)


def flash_attention_ref(q: jnp.ndarray, k: jnp.ndarray,
                        v: jnp.ndarray) -> jnp.ndarray:
    """Causal GQA attention oracle. q [B,S,H,D]; k/v [B,S,G,D]."""
    b, s, h, d = q.shape
    g = k.shape[2]
    rep = h // g
    kk = jnp.repeat(k, rep, axis=2)
    vv = jnp.repeat(v, rep, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, kk).astype(jnp.float32)
    scores = scores / math.sqrt(d)
    mask = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(mask, scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(q.dtype), vv)


def paged_decode_attention_ref(q: jnp.ndarray, k_store: jnp.ndarray,
                               v_store: jnp.ndarray, block_table: jnp.ndarray,
                               pos: jnp.ndarray) -> jnp.ndarray:
    """Paged GQA decode attention oracle, in f32. q [B,H,D]; k/v_store
    [N,bs,G,D]; block_table [B,W]; slot b attends over its keys
    0..pos[b]. Returns [B,H,D] f32."""
    b, h, d = q.shape
    _, bs, g, _ = k_store.shape
    w = block_table.shape[1]
    k = k_store[block_table].reshape(b, w * bs, g, d).astype(jnp.float32)
    v = v_store[block_table].reshape(b, w * bs, g, d).astype(jnp.float32)
    qg = q.reshape(b, g, h // g, d).astype(jnp.float32)
    hi = jax.lax.Precision.HIGHEST
    scores = jnp.einsum("bgrd,bkgd->bgrk", qg, k, precision=hi)
    scores = scores / math.sqrt(d)
    valid = jnp.arange(w * bs)[None] <= pos[:, None]
    scores = jnp.where(valid[:, None, None], scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bgrk,bkgd->bgrd", p, v, precision=hi).reshape(b, h, d)
