"""Pallas TPU kernels (validated in interpret mode on CPU):

  * ``pim_mac`` / ``pim_matmul`` — the paper's MAC/GEMM dataflow, TPU-tiled
  * ``pim_matmul_grouped`` / ``pim_mac_grouped`` — the same dataflow with a
                                   leading group axis: one launch covers a
                                   whole stack of placed blocks / a wave of
                                   eltwise MACs (subarray parallelism made
                                   explicit)
  * ``pim_fp32_mul``             — bit-serial shift-and-add f32 multiply
                                   (Fig. 4b), bit-exact IEEE-754
  * ``flash_attention``          — causal GQA attention, online softmax in
                                   VMEM scratch (never writes S x S to HBM)
  * ``paged_decode_attention_grouped`` — paged-KV decode attention for all
                                   batch slots and KV heads in one launch,
                                   copying each slot's pages (and none past
                                   its position) through a scalar-prefetched
                                   block table (``..._q``: one KV head and
                                   block a step over a quantized pool,
                                   dequantize-on-load)

Each kernel picks its mode itself (``repro.kernels.mode``): compiled on a
TPU, interpreted elsewhere. ``ref`` holds the pure-jnp oracles.
"""

from repro.kernels import ref
from repro.kernels.flash_attention import (flash_attention,
                                           paged_decode_attention_grouped,
                                           paged_decode_attention_grouped_q)
from repro.kernels.pim_fp import pim_fp32_mul
from repro.kernels.pim_mac import (pim_mac, pim_mac_grouped, pim_matmul,
                                   pim_matmul_grouped,
                                   pim_matmul_grouped_q)

__all__ = ["ref", "flash_attention", "paged_decode_attention_grouped",
           "paged_decode_attention_grouped_q",
           "pim_fp32_mul", "pim_mac", "pim_mac_grouped", "pim_matmul",
           "pim_matmul_grouped", "pim_matmul_grouped_q"]
