"""Plain reference of LeNet-5 training: its weights made from a seed, the
loss and gradients in float32, and AdamW, written out.

The network is the configuration file's: two 5x5 valid convolutions
(1 -> 6 -> 16 channels), each followed by tanh and 2x2 average pooling,
then fully connected layers 256 -> 64 -> 35 -> 10 with tanh between,
and the mean cross-entropy over the batch. Every product runs at the
precision the configuration states (``highest``: float32), with no
kernels and no placement; the control's ``high`` is written out. It
imports nothing of the program under test.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from models.qwen2 import seed_key

HI = jax.lax.Precision.HIGHEST


def _split(x):
    """``x`` as a bfloat16 head and a bfloat16 remainder."""
    hi = x.astype(jnp.bfloat16).astype(jnp.float32)
    return hi, (x - hi).astype(jnp.bfloat16).astype(jnp.float32)


def _product(op, x, w, precision: str):
    """``op(x, w)`` at the named precision. ``highest`` is float32;
    ``high`` is written out as its three bfloat16 passes (head x head,
    head x remainder, remainder x head, summed in float32), forward and
    backward, so that it means the same on every backend."""
    if precision == "highest":
        return op(x, w, HI)
    if precision != "high":
        raise ValueError(f"unknown precision {precision!r}")
    return _high(op, x, w)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _high(op, x, w):
    (xh, xl), (wh, wl) = _split(x), _split(w)
    return op(xh, wh, HI) + op(xh, wl, HI) + op(xl, wh, HI)


def _high_fwd(op, x, w):
    return _high(op, x, w), (x, w)


def _high_bwd(op, res, g):
    x, w = res
    (xh, xl), (wh, wl), (gh, gl) = _split(x), _split(w), _split(g)

    def dx(wv, gv):
        return jax.vjp(lambda a: op(a, wv, HI), x)[1](gv)[0]

    def dw(xv, gv):
        return jax.vjp(lambda b: op(xv, b, HI), w)[1](gv)[0]
    return (dx(wh, gh) + dx(wl, gh) + dx(wh, gl),
            dw(xh, gh) + dw(xl, gh) + dw(xh, gl))


_high.defvjp(_high_fwd, _high_bwd)


def _conv(x, w, prec):
    """A valid, stride-1 convolution written as one matmul over the
    image's patches (NHWC images, HWIO weights). A matmul and its
    gradients compile in seconds on a TPU at ``HIGHEST``, where XLA's
    own convolution gradients at that precision take many minutes."""
    kh, kw, cin, cout = w.shape
    b, h, wd, _ = x.shape
    oh, ow = h - kh + 1, wd - kw + 1
    patches = jnp.concatenate([x[:, i:i + oh, j:j + ow, :]
                               for i in range(kh) for j in range(kw)], -1)
    out = jnp.matmul(patches.reshape(b * oh * ow, kh * kw * cin),
                     w.reshape(kh * kw * cin, cout), precision=prec)
    return out.reshape(b, oh, ow, cout)


def _matmul(x, w, prec):
    return jnp.matmul(x, w, precision=prec)


def _params(cfg: dict, key) -> dict:
    c1, c2 = cfg["conv_channels"]
    k = cfg["kernel"]
    f1, f2 = cfg["fc_dims"]
    flat = c2 * ((((cfg["in_hw"] - k + 1) // 2) - k + 1) // 2) ** 2
    shapes = {"conv1": (k, k, cfg["in_channels"], c1), "conv2": (k, k, c1, c2),
              "fc1": (flat, f1), "fc2": (f1, f2), "fc3": (f2, cfg["n_classes"])}
    out = {}
    for i, (name, shp) in enumerate(shapes.items()):
        kw, kb = jax.random.split(jax.random.fold_in(key, i))
        fan = int(np.prod(shp[:-1]))
        out[name] = {
            "w": jax.random.normal(kw, shp, jnp.float32) * (2.0 / fan) ** 0.5,
            "b": jax.random.normal(kb, (shp[-1],), jnp.float32) * 0.1}
    return out


def make_params(cfg: dict, seed: int) -> dict:
    """Every weight, made on the device from the seed in one call."""
    fn = jax.jit(functools.partial(_params, cfg))
    return fn(seed_key(seed))


def _pool(x):
    b, h, w, c = x.shape
    return x.reshape(b, h // 2, 2, w // 2, 2, c).mean(axis=(2, 4))


def loss(params, images, labels, precision: str = "highest"):
    x = images
    for name in ("conv1", "conv2"):
        x = _product(_conv, x, params[name]["w"], precision)
        x = _pool(jnp.tanh(x + params[name]["b"]))
    x = x.reshape(x.shape[0], -1)
    for name in ("fc1", "fc2"):
        x = jnp.tanh(_product(_matmul, x, params[name]["w"], precision)
                     + params[name]["b"])
    logits = _product(_matmul, x, params["fc3"]["w"], precision) \
        + params["fc3"]["b"]
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))


def adamw(params, grads, m, v, t: int, opt: dict):
    """One AdamW step (bias-corrected moments, decoupled weight decay)."""
    b1, b2 = opt["b1"], opt["b2"]
    m = jax.tree.map(lambda a, g: b1 * a + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda a, g: b2 * a + (1 - b2) * g * g, v, grads)
    c1, c2 = 1 - b1 ** t, 1 - b2 ** t
    params = jax.tree.map(
        lambda p, a, b: p - opt["lr"] * ((a / c1) / (jnp.sqrt(b / c2)
                                                     + opt["eps"])
                                         + opt["weight_decay"] * p),
        params, m, v)
    return params, m, v


def run(cfg: dict, params, batches, precision: str = "highest") -> dict:
    """``len(batches)`` training steps from ``params``: each step's loss,
    the first step's gradient and the parameters after the last."""
    opt = cfg["optimizer"]
    grad_fn = jax.jit(jax.value_and_grad(functools.partial(
        loss, precision=precision)))
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    losses, first = [], None
    for t, (imgs, labels) in enumerate(batches, start=1):
        val, g = grad_fn(params, jnp.asarray(imgs), jnp.asarray(labels))
        if first is None:
            first = g
        losses.append(float(val))
        params, m, v = adamw(params, g, m, v, t, opt)
    return {"losses": losses, "grad": first, "params": params}
