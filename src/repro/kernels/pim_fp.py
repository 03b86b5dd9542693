"""Bit-serial IEEE-754 f32 multiply as a Pallas kernel — the in-kernel
analogue of the paper's §3.3 mantissa shift-and-add (Fig. 4b).

Faithfulness map:
  * the 24-step ``fori_loop`` over multiplier bits = the bit-serial row
    schedule of the subarray;
  * the VMEM lanes of the tile = the 1024 column-parallel MACs;
  * the (lo, hi) 24-bit limb pair = the paper's two ping-pong accumulator
    columns (the partial product is never written back to HBM — FloatPIM's
    455-cell intermediate writes are exactly what this avoids);
  * rounding is IEEE round-to-nearest-even, bit-exact vs XLA's native f32
    multiply (tests/test_kernels.py sweeps random + edge-case inputs).

Subnormal inputs/outputs flush to zero (same contract as repro.core.fp).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.mode import resolve_interpret


def _pim_fp32_mul_kernel(a_ref, b_ref, o_ref):
    # masks built in-kernel (module-level jnp constants would be captured
    # as consts, which pallas_call rejects)
    _M24 = jnp.uint32(0xFFFFFF)
    _M23 = jnp.uint32(0x7FFFFF)
    a = a_ref[...]
    b = b_ref[...]
    ua = jax.lax.bitcast_convert_type(a, jnp.uint32)
    ub = jax.lax.bitcast_convert_type(b, jnp.uint32)
    sa = ua >> 31
    sb = ub >> 31
    ea = (ua >> 23) & jnp.uint32(0xFF)
    eb = (ub >> 23) & jnp.uint32(0xFF)
    sig_a = (ua & _M23) | jnp.uint32(1 << 23)
    sig_b = (ub & _M23) | jnp.uint32(1 << 23)

    # 24-step shift-and-add into ping-pong 24-bit limbs (lo, hi)
    def step(i, carry):
        lo, hi = carry
        bit = (sig_b >> i) & jnp.uint32(1)
        keep_mask = (jnp.uint32(1) << (jnp.uint32(24) - i)) - jnp.uint32(1)
        lo = lo + bit * ((sig_a & keep_mask) << i)
        hi = hi + bit * (sig_a >> (jnp.uint32(24) - i))
        hi = hi + (lo >> 24)          # carry propagate
        lo = lo & _M24
        return lo, hi

    lo0 = jnp.zeros_like(ua)
    hi0 = jnp.zeros_like(ua)
    lo, hi = jax.lax.fori_loop(0, 24, step, (lo0, hi0))

    # product in [2^46, 2^48): normalize by top bit (47)
    top = (hi >> 23) & jnp.uint32(1)
    keep1 = hi                                     # bits 24..47
    g1 = (lo >> 23) & jnp.uint32(1)
    s1 = (lo & _M23) != 0
    keep0 = ((hi << 1) | (lo >> 23)) & _M24        # bits 23..46
    g0 = (lo >> 22) & jnp.uint32(1)
    s0 = (lo & jnp.uint32(0x3FFFFF)) != 0
    keep = jnp.where(top == 1, keep1, keep0)
    guard = jnp.where(top == 1, g1, g0)
    sticky = jnp.where(top == 1, s1, s0)

    inc = guard & (sticky.astype(jnp.uint32) | (keep & jnp.uint32(1)))
    keep = keep + inc
    round_ovf = (keep >> 24) & jnp.uint32(1)
    keep = jnp.where(round_ovf == 1, keep >> 1, keep)

    e = (ea.astype(jnp.int32) + eb.astype(jnp.int32) - 127
         + top.astype(jnp.int32) + round_ovf.astype(jnp.int32))
    s_res = sa ^ sb
    mant = keep & _M23
    underflow = e <= 0
    overflow = e >= 255
    e_u = jnp.clip(e, 0, 255).astype(jnp.uint32)
    out_u = (s_res << 31) | (e_u << 23) | mant
    out_u = jnp.where(underflow, s_res << 31, out_u)
    out_u = jnp.where(overflow, (s_res << 31) | jnp.uint32(0x7F800000),
                      out_u)
    res = jax.lax.bitcast_convert_type(out_u, jnp.float32)

    # specials (zero/subnormal-FTZ inputs, inf, nan) -> native semantics
    special = ((ea == 0) | (eb == 0) | (ea == 255) | (eb == 255))
    o_ref[...] = jnp.where(special, a * b, res)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def pim_fp32_mul(a: jnp.ndarray, b: jnp.ndarray, *, block: int = 1024,
                 interpret: bool | None = None) -> jnp.ndarray:
    """Elementwise bit-exact f32 multiply via the PIM shift-and-add."""
    assert a.shape == b.shape
    orig = a.shape
    n = a.size
    pad = (-n) % block
    a2 = jnp.pad(a.reshape(-1), (0, pad), constant_values=1.0
                 ).reshape(-1, block)
    b2 = jnp.pad(b.reshape(-1), (0, pad), constant_values=1.0
                 ).reshape(-1, block)
    rows = a2.shape[0]
    out = pl.pallas_call(
        _pim_fp32_mul_kernel,
        grid=(rows,),
        in_specs=[pl.BlockSpec((1, block), lambda i: (i, 0))] * 2,
        out_specs=pl.BlockSpec((1, block), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, block), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(a2, b2)
    return out.reshape(-1)[:n].reshape(orig)
