"""Operations and bytes that the model needs, counted from its shapes.

These are the numerators of every roofline share and MFU the benchmark
reports. They count the work of the model as published, not the work
the program happens to do: a placed matmul is ``2*m*k*n`` operations at
its unpadded shape, and its bytes are the weights at the configuration's
dtype plus the activations read and written. A program that pads, re-lays
out or recomputes does more work than this; its share is then lower.

Only matrix products and attention are counted: norms, activations,
rotary embeddings and softmax are a few operations per element and are
left out, so a share computed from these counts is a lower bound.
"""

from __future__ import annotations

import dataclasses
import json
import pathlib

PEAKS_FILE = pathlib.Path(__file__).resolve().parent / "peaks.json"


@dataclasses.dataclass(frozen=True)
class Work:
    flops: float = 0.0
    bytes: float = 0.0

    def __add__(self, other: "Work") -> "Work":
        return Work(self.flops + other.flops, self.bytes + other.bytes)

    def scale(self, f: float) -> "Work":
        return Work(self.flops * f, self.bytes * f)


def peaks(device_kind: str) -> dict:
    """The chip's published peaks. A device that is not in the table is
    an error: a roofline against a guessed peak means nothing."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def least_seconds(w: Work, pk: dict) -> float:
    """The least time the chip could take: the larger of the compute
    bound and the memory bound."""
    return max(w.flops / pk["bf16_flops_per_s"], w.bytes / pk["hbm_bytes_per_s"])


def matmul(m: int, k: int, n: int, *, w_bytes: int = 2,
           act_bytes: int = 2) -> Work:
    """``[m, k] @ [k, n]``: the weight is read once, the activation read
    and the product written at ``act_bytes`` each."""
    return Work(2.0 * m * k * n,
                float(k * n * w_bytes + (m * k + m * n) * act_bytes))


# ---------------------------------------------------------------------------
# decoder-only transformer (qwen2 family)
# ---------------------------------------------------------------------------


def decoder_matmuls(cfg: dict, m: int) -> list[Work]:
    """Every weight matmul of one pass of ``m`` token rows: per layer the
    q, k, v, o projections and the gate, up and down projections, then
    the LM head. ``cfg`` holds the configuration file's keys."""
    d = cfg["hidden_size"]
    f = cfg["intermediate_size"]
    hd = d // cfg["num_attention_heads"]
    q = cfg["num_attention_heads"] * hd
    kv = cfg["num_key_value_heads"] * hd
    wb = _dtype_bytes(cfg["torch_dtype"])
    one = [matmul(m, d, q, w_bytes=wb, act_bytes=wb),
           matmul(m, d, kv, w_bytes=wb, act_bytes=wb),
           matmul(m, d, kv, w_bytes=wb, act_bytes=wb),
           matmul(m, q, d, w_bytes=wb, act_bytes=wb),
           matmul(m, d, f, w_bytes=wb, act_bytes=wb),
           matmul(m, d, f, w_bytes=wb, act_bytes=wb),
           matmul(m, f, d, w_bytes=wb, act_bytes=wb)]
    out = one * cfg["num_hidden_layers"]
    out.append(matmul(m, d, cfg["vocab_size"], w_bytes=wb, act_bytes=wb))
    return out


def decode_attention(cfg: dict, lengths) -> Work:
    """Paged decode attention of one tick over every layer: each slot's
    new query attends to its ``length`` cached keys (``length`` counts
    the new token). Scores and the value pass are ``4 * heads * hd``
    operations per key; the K and V of every key are read once per
    layer at the cache's dtype, the query read and the output written."""
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    g = cfg["num_key_value_heads"]
    hd = d // h
    b = _dtype_bytes(cfg["torch_dtype"])
    keys = float(sum(lengths))
    rows = len(lengths)
    per_layer = Work(4.0 * h * hd * keys,
                     2.0 * g * hd * b * keys + 2.0 * rows * h * hd * b)
    return per_layer.scale(cfg["num_hidden_layers"])


def prefill_attention(cfg: dict, n: int) -> Work:
    """Causal attention of an ``n``-token prompt over itself, every
    layer: query ``i`` sees ``i + 1`` keys."""
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    g = cfg["num_key_value_heads"]
    hd = d // h
    b = _dtype_bytes(cfg["torch_dtype"])
    pairs = n * (n + 1) / 2.0
    per_layer = Work(4.0 * h * hd * pairs, 2.0 * g * hd * b * n + 2.0 * n * h * hd * b)
    return per_layer.scale(cfg["num_hidden_layers"])


def prefill(cfg: dict, n: int) -> Work:
    """One prompt of ``n`` tokens written into the cache: the layers'
    matmuls and causal attention. The LM head is not run in a prefill."""
    layers = decoder_matmuls(cfg, n)[:-1]
    return sum(layers, Work()) + prefill_attention(cfg, n)


def decode_tick(cfg: dict, lengths) -> Work:
    """One decode tick over the active slots, ``lengths`` their cached
    lengths with the new token."""
    return (sum(decoder_matmuls(cfg, len(lengths)), Work())
            + decode_attention(cfg, lengths))


# ---------------------------------------------------------------------------
# LeNet-5 (the paper's network)
# ---------------------------------------------------------------------------


def lenet_layers(cfg: dict) -> list[tuple[str, int, int, int]]:
    """LeNet's weight layers as matrix products per image: ``(name,
    rows, depth, cols)``, a convolution counted as its output pixels
    times its receptive field times its output channels."""
    hw = cfg["in_hw"]
    c1, c2 = cfg["conv_channels"]
    ks = cfg["kernel"]
    f1, f2 = cfg["fc_dims"]
    o1 = hw - ks + 1                 # 28 -> 24, pooled to 12
    o2 = o1 // 2 - ks + 1            # 12 -> 8, pooled to 4
    flat = c2 * (o2 // 2) ** 2
    return [("conv1", o1 * o1, ks * ks * 1, c1),
            ("conv2", o2 * o2, ks * ks * c1, c2),
            ("fc1", 1, flat, f1),
            ("fc2", 1, f1, f2),
            ("fc3", 1, f2, cfg["n_classes"])]


def lenet_step_flops_per_image(cfg: dict) -> float:
    """Forward and backward operations of one training image: the
    forward product of every layer, its weight gradient, and its input
    gradient except the first layer's (no gradient flows into the
    image)."""
    total = 0.0
    for i, (_, r, k, c) in enumerate(lenet_layers(cfg)):
        fwd = 2.0 * r * k * c
        total += fwd * (2 if i == 0 else 3)
    return total


def lenet_step_matmuls(cfg: dict, batch: int) -> list[Work]:
    """The step's matrix products at batch ``batch``, f32 operands: per
    layer the forward product, the weight gradient and (except the
    first) the input gradient, each reading its operands and writing
    its result once."""
    out = []
    for i, (_, r, k, c) in enumerate(lenet_layers(cfg)):
        m = batch * r
        out.append(matmul(m, k, c, w_bytes=4, act_bytes=4))       # forward
        out.append(Work(2.0 * m * k * c, 4.0 * (m * k + m * c + k * c)))  # dW
        if i:
            out.append(matmul(m, c, k, w_bytes=4, act_bytes=4))   # dX
    return out


def _dtype_bytes(name: str) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[name]
