"""Pallas kernels vs pure-jnp oracles — shape/dtype sweeps, interpret mode.

Per the kernel contract: each kernel sweeps shapes/dtypes and asserts
allclose (bit-equal for the FP kernel) against ``repro.kernels.ref``.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from repro.kernels import ref
from repro.kernels.flash_attention import (flash_attention,
                                          paged_decode_attention_grouped)
from repro.kernels.pim_fp import pim_fp32_mul
from repro.kernels.pim_mac import pim_mac, pim_matmul


@pytest.mark.parametrize("shape", [(64,), (1000,), (7, 130)])
@pytest.mark.parametrize("block", [128, 512])
def test_pim_mac_sweep(rng, shape, block):
    a = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    b = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    acc = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    got = pim_mac(a, b, acc, block=block)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(ref.pim_mac_ref(a, b, acc)),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("mnk", [(128, 128, 128), (256, 128, 384),
                                 (384, 256, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pim_matmul_sweep(rng, mnk, dtype):
    m, n, k = mnk
    a = jnp.asarray(rng.standard_normal((m, k)), dtype)
    b = jnp.asarray(rng.standard_normal((k, n)), dtype)
    got = pim_matmul(a, b)
    want = ref.pim_matmul_ref(a, b)
    tol = 1e-4 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("bshgd", [(1, 128, 4, 2, 64), (2, 128, 8, 8, 32),
                                   (1, 64, 6, 3, 16)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(rng, bshgd, dtype):
    b, s, h, g, d = bshgd
    q = jnp.asarray(rng.standard_normal((b, s, h, d)), dtype)
    k = jnp.asarray(rng.standard_normal((b, s, g, d)), dtype)
    v = jnp.asarray(rng.standard_normal((b, s, g, d)), dtype)
    got = flash_attention(q, k, v, q_chunk=64, kv_chunk=64)
    want = ref.flash_attention_ref(q, k, v)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def _paged_case(rng, batch, block_size, blocks_per_slot):
    """A pool, table and positions with ragged lengths: positions 0,
    bs - 1, bs and W * bs - 1 (a full table), then random ones; the
    last lane is inactive (position 0 on the scratch block 0, as the
    engine leaves a freed slot)."""
    n_blocks = 1 + batch * blocks_per_slot
    full = blocks_per_slot * block_size
    pos = np.concatenate([[0, block_size - 1, block_size, full - 1],
                          rng.integers(0, full, batch - 5), [0]])
    table = np.zeros((batch, blocks_per_slot), np.int32)
    ids = rng.permutation(np.arange(1, n_blocks))
    for s in range(batch - 1):
        used = pos[s] // block_size + 1
        table[s, :used] = ids[s * blocks_per_slot:s * blocks_per_slot + used]
    return n_blocks, jnp.asarray(table), jnp.asarray(pos, jnp.int32)


# (bs, W): 128 // bs pages a step clipped to W, so (8, 8) streams the
# whole table in one step, (8, 20) and (16, 12) end in a partial step
@pytest.mark.parametrize("bs_w", [(8, 8), (8, 20), (16, 8), (16, 12)])
@pytest.mark.parametrize("heads", [8, 40])            # rep 1 and 5
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_decode_attention_matches_reference(rng, bs_w, heads, dtype):
    bs, w = bs_w
    batch, kv_heads, head_dim = 8, 8, 128
    n_blocks, table, pos = _paged_case(rng, batch, bs, w)
    pool = (n_blocks, bs, kv_heads, head_dim)
    q = jnp.asarray(rng.standard_normal((batch, heads, head_dim)), dtype)
    k = jnp.asarray(rng.standard_normal(pool), dtype)
    v = jnp.asarray(rng.standard_normal(pool), dtype)
    got = paged_decode_attention_grouped(q, k, v, table, pos)
    want = ref.paged_decode_attention_ref(q, k, v, table, pos)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), rtol=tol, atol=tol)


def test_pim_fp32_mul_bitexact_random(rng):
    a = (rng.standard_normal(8192) * np.exp(rng.uniform(-30, 30, 8192))
         ).astype(np.float32)
    b = (rng.standard_normal(8192) * np.exp(rng.uniform(-30, 30, 8192))
         ).astype(np.float32)
    got = np.asarray(pim_fp32_mul(jnp.asarray(a), jnp.asarray(b),
                                  block=1024))
    want = a * b
    ok = (got.view(np.uint32) == want.view(np.uint32)) | (
        np.isnan(got) & np.isnan(want))
    assert ok.all()


def test_pim_fp32_mul_edges():
    a = np.array([1e30, 1e30, 1e-30, 1.0, -0.0, np.inf, 1.5, 3.0,
                  1 + 2 ** -23], np.float32)
    b = np.array([1e30, -1e30, 1e-30, 0.0, 2.0, 2.0, 1.5, 1 + 2 ** -23,
                  1 + 2 ** -23], np.float32)
    got = np.asarray(pim_fp32_mul(jnp.asarray(a), jnp.asarray(b), block=16))
    want = a * b
    np.testing.assert_array_equal(got.view(np.uint32),
                                  want.view(np.uint32))
