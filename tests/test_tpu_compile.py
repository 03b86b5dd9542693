"""The main path's Pallas kernels compile for a TPU v5e at real widths.

Each test compiles one kernel with an explicit ``interpret=False`` for a
*described* v5e chip (``jax.experimental.topologies``): nothing runs, so
this needs no chip, but the TPU compiler refuses here whatever it would
refuse there (block shapes off the 8x128 tiling, VMEM overuse). The
topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and every test worker
imports this file.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.lenet5 import CONFIG as LENET
from repro.kernels import (paged_decode_attention_grouped,
                           paged_decode_attention_grouped_q, pim_mac,
                           pim_matmul_grouped)
from repro.kernels.pim_mac import pim_mac_grouped, pim_matmul_grouped_q
from repro.models import lenet

# qwen2.5-32b: d_model 5120, d_ff 27648, 40 query / 8 KV heads of 128
D_MODEL, D_FF, HEADS, KV_HEADS, HEAD_DIM = 5120, 27648, 40, 8, 128
# one subarray holds a 921 x 32 block of f32 weights (1024 x 1024 cells)
SUB_ROWS, SUB_COLS, TILE = 921, 32, 128


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no TPU here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache: keep it out of any cache this test run has set
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _shape(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _mlp_block_grid(one_chip):
    """The gate projection's stacked operands at one decode tick: R row
    chunks of activations (8 rows padded to a tile) and R*C padded
    subarray blocks, C column blocks sharing each activation slab."""
    rows = -(-D_MODEL // SUB_ROWS)
    cols = -(-D_FF // SUB_COLS)
    kb = -(-SUB_ROWS // TILE) * TILE
    a = _shape(one_chip, (rows, TILE, kb))
    b = _shape(one_chip, (rows * cols, kb, TILE))
    return a, b, cols


def test_pim_matmul_grouped_compiles_at_qwen_mlp_grid(one_chip):
    a, b, cols = _mlp_block_grid(one_chip)
    text = _compile_text(lambda a, b: pim_matmul_grouped(
        a, b, interpret=False, col_groups=cols), a, b)
    assert "tpu_custom_call" in text


def test_pim_matmul_grouped_grad_compiles_at_qwen_mlp_grid(one_chip):
    a, b, cols = _mlp_block_grid(one_chip)

    def loss(a, b):
        return jnp.sum(pim_matmul_grouped(a, b, interpret=False,
                                          col_groups=cols))

    text = _compile_text(jax.value_and_grad(loss, argnums=(0, 1)), a, b)
    assert text.count("tpu_custom_call") >= 3     # forward, dA and dB


@pytest.mark.parametrize("wave", [1, 8])
def test_pim_mac_compiles_at_lenet_optimizer_wave(one_chip, wave):
    """One eltwise wave over every LeNet-5 parameter (``wave`` update
    equations fused into one launch)."""
    params = jax.eval_shape(lambda k: lenet.init_lenet(k, LENET),
                            jax.random.PRNGKey(0))
    n = wave * sum(x.size for x in jax.tree.leaves(params))
    x = _shape(one_chip, (n,))
    text = _compile_text(lambda a, b, c: pim_mac(a, b, c, interpret=False),
                         x, x, x)
    assert "tpu_custom_call" in text


def _paged_operands(one_chip, pool_dtype, *, batch=8, block_size=16,
                    blocks_per_slot=8, n_blocks=None):
    n_blocks = n_blocks or 1 + batch * blocks_per_slot
    pool = (n_blocks, block_size, KV_HEADS, HEAD_DIM)
    return dict(
        q=_shape(one_chip, (batch, HEADS, HEAD_DIM), jnp.bfloat16),
        pool=_shape(one_chip, pool, pool_dtype),
        scale=_shape(one_chip, pool[:3] + (1,)),
        table=_shape(one_chip, (batch, blocks_per_slot), jnp.int32),
        pos=_shape(one_chip, (batch,), jnp.int32))


# a small pool, and the chat benchmark's: 128 slots of up to 144 blocks
# of 8 tokens in a 12,000-block pool
@pytest.mark.parametrize("shape", [
    {}, dict(batch=128, block_size=8, blocks_per_slot=144, n_blocks=12000)],
    ids=["small", "chat"])
def test_paged_decode_attention_compiles_at_qwen_heads(one_chip, shape):
    o = _paged_operands(one_chip, jnp.bfloat16, **shape)
    text = _compile_text(lambda q, k, v, t, p: paged_decode_attention_grouped(
        q, k, v, t, p, interpret=False),
        o["q"], o["pool"], o["pool"], o["table"], o["pos"])
    assert "tpu_custom_call" in text


def test_paged_decode_attention_q_compiles_at_qwen_heads(one_chip):
    o = _paged_operands(one_chip, jnp.int8)

    def attend(q, k, ks, v, vs, t, p):
        return paged_decode_attention_grouped_q(
            q, k, ks, v, vs, t, p, kv_dtype="int8", interpret=False)

    text = _compile_text(attend, o["q"], o["pool"], o["scale"], o["pool"],
                         o["scale"], o["table"], o["pos"])
    assert "tpu_custom_call" in text


_CUSTOM_CALL = re.compile(r"^\s*(?:ROOT )?%([A-Za-z_][\w\-]*?)(?:\.\d+)? = "
                          r".*custom_call_target=\"tpu_custom_call\"", re.M)


def _kernel_names(text: str) -> list[str]:
    """The instruction names of a compiled program's Pallas calls (what a
    profile shows as their op names), without the numeric suffix."""
    return _CUSTOM_CALL.findall(text)


def test_pim_kernels_keep_their_names_forward_and_backward(one_chip):
    """A profile's op names for the placed kernels are fixed by the
    kernels themselves, not by the function that calls them or by the
    transformation (grad) that produced them: the benchmark's roofline
    readers find them as ``pim_matmul*``."""
    a = _shape(one_chip, (2, 128, 256))
    b = _shape(one_chip, (2, 256, 128))

    def fn(a, b):
        return pim_matmul_grouped(a, b, interpret=False)

    assert _kernel_names(_compile_text(fn, a, b)) == ["pim_matmul_grouped"]
    grad = jax.grad(lambda a, b: jnp.sum(fn(a, b)), argnums=(0, 1))
    assert _kernel_names(_compile_text(grad, a, b)) == \
        ["pim_matmul_grouped"] * 2

    q = _shape(one_chip, (2, 256, 128))           # f32-carried codes
    sc = _shape(one_chip, (2, 1, 128))
    assert _kernel_names(_compile_text(
        lambda a, q, s: pim_matmul_grouped_q(a, q, s, interpret=False),
        a, q, sc)) == ["pim_matmul_grouped_q"]

    x = _shape(one_chip, (300,))
    wave = _compile_text(lambda x, y, z: pim_mac_grouped(
        [(x, y, z), (y, z, x)], interpret=False), x, x, x)
    assert _kernel_names(wave) == ["pim_mac"]
