"""Plain reference of a Qwen2 decoder, and its weights made from a seed.

Follows the published Qwen2 architecture (``Qwen2ForCausalLM``): token
embedding; per layer a pre-norm RMSNorm, grouped-query attention with
biases on q, k and v, rotate-half rotary embeddings, a pre-norm RMSNorm
and a SwiGLU MLP, each added to the residual; a final RMSNorm and an
untied LM head. Everything is float32 at the highest matmul precision,
one sequence at a time, no cache and no kernels. It imports nothing of
the program under test.

The weights are this file's own: ``make_weights`` draws them on the
device from the seed in one jitted call, in the configuration's dtype.
The benchmark hands them to the program, and the reference draws them
again after the program's state is freed.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
# query rows per attention block in the reference: bounds the score
# matrix to heads x 1024 x length floats
Q_BLOCK = 1024


def seed_key(seed: int):
    """A key for any whole number up to 64 bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    return dict(d=d, f=cfg["intermediate_size"], h=h,
                g=cfg["num_key_value_heads"], hd=d // h,
                layers=cfg["num_hidden_layers"], vocab=cfg["vocab_size"])


def _weights(cfg: dict, key) -> dict:
    n = dims(cfg)
    d, f, hd = n["d"], n["f"], n["hd"]
    q, kv = n["h"] * hd, n["g"] * hd
    dt = jnp.dtype(cfg["torch_dtype"])

    def normal(k, shape, scale):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dt)

    def layer(i):
        ks = jax.random.split(jax.random.fold_in(key, 1000 + i), 12)
        return {
            "norm1": normal(ks[0], (d,), 0.1) + jnp.asarray(1, dt),
            "wq": normal(ks[1], (d, q), d ** -0.5),
            "bq": normal(ks[2], (q,), 0.5),
            "wk": normal(ks[3], (d, kv), d ** -0.5),
            "bk": normal(ks[4], (kv,), 0.5),
            "wv": normal(ks[5], (d, kv), d ** -0.5),
            "bv": normal(ks[6], (kv,), 0.5),
            "wo": normal(ks[7], (q, d), q ** -0.5),
            "norm2": normal(ks[8], (d,), 0.1) + jnp.asarray(1, dt),
            "w_gate": normal(ks[9], (d, f), d ** -0.5),
            "w_up": normal(ks[10], (d, f), d ** -0.5),
            "w_down": normal(ks[11], (f, d), f ** -0.5),
        }

    per = [layer(i) for i in range(n["layers"])]
    ke, kh, kn = jax.random.split(jax.random.fold_in(key, 7), 3)
    return {
        "embed": normal(ke, (n["vocab"], d), 1.0),
        "lm_head": normal(kh, (d, n["vocab"]), d ** -0.5),
        "final_norm": normal(kn, (d,), 0.1) + jnp.asarray(1, dt),
        "layers": {k: jnp.stack([p[k] for p in per]) for k in per[0]},
    }


@functools.lru_cache(maxsize=None)
def _weights_fn(cfg_items):
    return jax.jit(functools.partial(_weights, dict(cfg_items)))


def make_weights(cfg: dict, seed: int) -> dict:
    """Every weight, stacked over layers, made on the device from the
    seed in one jitted call."""
    keys = ("hidden_size", "intermediate_size", "num_attention_heads",
            "num_key_value_heads", "num_hidden_layers", "vocab_size",
            "torch_dtype")
    return _weights_fn(tuple((k, cfg[k]) for k in keys))(seed_key(seed))


# ---------------------------------------------------------------------------
# the reference forward pass
# ---------------------------------------------------------------------------


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    """Rotate-half rotary embedding; x [S, heads, hd], pos [S]."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos[:, None].astype(jnp.float32) * inv[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v):
    """Causal grouped-query attention; q [S, H, hd], k/v [S, G, hd]."""
    s, h, hd = q.shape
    g = k.shape[1]
    k = jnp.repeat(k, h // g, axis=1)
    v = jnp.repeat(v, h // g, axis=1)
    outs = []
    for q0 in range(0, s, Q_BLOCK):
        qb = q[q0:q0 + Q_BLOCK]
        sc = jnp.einsum("qhd,khd->hqk", qb, k, precision=HI) / np.sqrt(hd)
        rows = q0 + jnp.arange(qb.shape[0])[:, None]
        sc = jnp.where(jnp.arange(s)[None] <= rows, sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        outs.append(jnp.einsum("hqk,khd->qhd", p, v, precision=HI))
    return jnp.concatenate(outs, 0)


def _linear(x, w, quant):
    w = w.astype(jnp.float32)
    if quant is not None:
        x, w = quant(x, 1), quant(w, 0)
    return jnp.matmul(x, w, precision=HI)


def _forward(cfg, w, tokens, rows, quant=None):
    n = dims(cfg)
    eps = cfg["rms_norm_eps"]
    theta = cfg["rope_theta"]
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    x = f32(w["embed"][tokens])
    s = tokens.shape[0]
    pos = jnp.arange(s)
    lw = w["layers"]
    for i in range(n["layers"]):
        h = _rms(x, f32(lw["norm1"][i]), eps)
        q = _linear(h, lw["wq"][i], quant) + f32(lw["bq"][i])
        k = _linear(h, lw["wk"][i], quant) + f32(lw["bk"][i])
        v = _linear(h, lw["wv"][i], quant) + f32(lw["bv"][i])
        q = _rope(q.reshape(s, n["h"], n["hd"]), pos, theta)
        k = _rope(k.reshape(s, n["g"], n["hd"]), pos, theta)
        v = v.reshape(s, n["g"], n["hd"])
        a = _attention(q, k, v).reshape(s, n["h"] * n["hd"])
        x = x + _linear(a, lw["wo"][i], quant)
        h = _rms(x, f32(lw["norm2"][i]), eps)
        m = (jax.nn.silu(_linear(h, lw["w_gate"][i], quant))
             * _linear(h, lw["w_up"][i], quant))
        x = x + _linear(m, lw["w_down"][i], quant)
    x = _rms(x[rows], f32(w["final_norm"]), eps)
    return _linear(x, w["lm_head"], quant)


def fp8_e4m3(x, axis):
    """Round to float8 e4m3 with one absmax scale per slice along
    ``axis`` (per output column of a weight, per row of an activation):
    the precision step below bfloat16 that a serving stack would take."""
    red = 0 if axis == 0 else -1
    scale = jnp.max(jnp.abs(x), axis=red, keepdims=True) / 448.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


@functools.lru_cache(maxsize=None)
def _forward_fn(cfg_items, quantized: bool):
    return jax.jit(functools.partial(_forward, dict(cfg_items),
                                     quant=fp8_e4m3 if quantized else None))


def logits(cfg: dict, weights: dict, tokens, rows, *,
           quantized: bool = False) -> np.ndarray:
    """Reference logits [len(rows), vocab] of one sequence at ``rows``.
    The sequence is padded to a multiple of 512 and the rows to a
    multiple of 128 (causal, so the padding changes nothing before it)
    to bound the number of compiles."""
    tokens = np.asarray(tokens, np.int32)
    rows = np.asarray(rows, np.int32)
    s, r = len(tokens), len(rows)
    toks = np.zeros(-(-s // 512) * 512, np.int32)
    toks[:s] = tokens
    rws = np.full(-(-r // 128) * 128, rows[-1], np.int32)
    rws[:r] = rows
    fn = _forward_fn(tuple(sorted((k, v) for k, v in cfg.items()
                                  if isinstance(v, (int, float, str)))),
                     quantized)
    return np.asarray(fn(weights, jnp.asarray(toks), jnp.asarray(rws)))[:r]


def served_gaps(cfg: dict, weights: dict, prompt, out, *,
                control: bool = False) -> np.ndarray:
    """For each served token of one request: how far the reference's
    logit of that token lies below the reference's best at its position.
    With ``control`` the token is the one the fp8 control puts first at
    that position instead of the served one."""
    prompt = np.asarray(prompt, np.int32)
    out = np.asarray(out, np.int32)
    seq = np.concatenate([prompt, out[:-1]])
    rows = np.arange(len(prompt) - 1, len(seq))
    ref = logits(cfg, weights, seq, rows)
    toks = out
    if control:
        toks = np.argmax(logits(cfg, weights, seq, rows, quantized=True), -1)
    return ref.max(-1) - ref[np.arange(len(rows)), toks]
