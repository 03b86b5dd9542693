"""Prove that the PIM serving and training paths run on a TPU.

    python chip_smoke.py                # one chip: the serve and train phases
    python chip_smoke.py --four-chips   # four chips: device-pinned pipeline

One chip runs two phases through the entry points a user calls:

* **serve** — qwen2.5-32b at its published widths (d_model 5120, 40 query
  and 8 KV heads of 128, d_ff 27648, QKV bias, rope theta 1e6), cut to
  2 layers and to the chip's 1/8 share of the vocabulary (19008 rows),
  with seeded random bf16 weights. ``ServeEngine(backend="pim",
  paged=True, prefill="batch", attn_kernel=True, expand_scans=True)``
  answers 8 requests of 64-128 prompt tokens and 16 new tokens each. On
  one cache state and one input, the pim decode step's logits are
  compared with ``jax.jit(model.decode_step_paged)`` (the XLA attention
  path) at the highest matmul precision.
* **train** — the paper's LeNet-5 at its published size: 5 steps of
  ``Trainer(backend="pim")`` and 5 of ``Trainer(backend="jit")`` from
  one seed, whose losses and parameter changes must agree.

``--four-chips`` runs only the device-pinned pipeline and what it is
compared with: qwen2.5-32b at the same widths cut to 4 layers,
``ServeEngine(partitions=4, expand_scans=True, pim_compile={"devices":
...})`` against the same partitioned engine unpinned.

The script exits non-zero, printing no result, unless JAX's first device
is a TPU, and when any check fails. Its last stdout line is one JSON
object, ``{"ok": true, "device": {"platform", "kind", "count"}}``. JAX's
persistent compilation cache lives where ``JAX_COMPILATION_CACHE_DIR``
says, else in ``.jax_cache`` next to this file.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import pathlib
import sys
import tempfile
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import configs  # noqa: E402
from repro.configs.lenet5 import CONFIG as LENET  # noqa: E402
from repro.data import DigitsDataset  # noqa: E402
from repro.kernels.mode import resolve_interpret  # noqa: E402
from repro.models import lenet  # noqa: E402
from repro.models.transformer import build_model  # noqa: E402
from repro.optim import make_optimizer  # noqa: E402
from repro.serve import Request, ServeEngine  # noqa: E402
from repro.train import Trainer, TrainerConfig  # noqa: E402

# The chip's share of the LM head: a 1/8 slice of qwen2.5's 152064 rows.
# The placed LM head is padded to 1024x128 f32 subarray blocks (about
# 4.45x its bf16 bytes); the whole head would need ~15 GB in one call.
VOCAB_SHARE = 8

# Serve: max |pim - ref| over max |ref| of the decode-step logits. Both
# programs round every matmul output to the model's bf16 (2^-9 relative
# each); they differ in accumulation order and in where that rounding
# falls (the pim program sums 921-row subarray partials in f32 before it
# casts), and 2 layers plus the LM head compound it: at d_model 512 and
# 1024 in bf16 the CPU measures 1.1-1.3e-2. 2^-4 admits a few bf16
# roundings of the largest logit and no structural error: a wrong KV
# gather, head mapping or block order moves logits by O(max |ref|).
SERVE_LOGITS_TOL = 2.0 ** -4

# Train: 5 LeNet steps at the default matmul precision (at "highest" the
# TPU compiler spends over 20 minutes on the jit step's multi-pass
# convolution gradients). There the placed Pallas matmuls and XLA's
# convs and dots both round f32 operands to bf16 for one MXU pass with
# f32 accumulation, and LeNet's contractions (25 to 256 deep) fit one
# 921-row subarray block, so the two programs do the same arithmetic:
# the chip measured bit-identical losses and fc parameters, and conv
# parameter changes 7e-7 apart (f32 summation order in the conv
# gradients). On the CPU both are exact f32 and differ in summation
# order only (~1e-6).
#
# Per-step |loss_pim - loss_jit| / |loss_jit|. 2^-7 is a few bf16
# roundings (2^-9 each) of the mean loss, should a compiler ever round
# one product differently in the two programs.
TRAIN_LOSS_RTOL = 2.0 ** -7
# Per parameter leaf, |dpim - djit| / |djit| in the L2 norm, where d is
# the leaf's change over the 5 steps. The losses alone barely see the
# optimizer: batch-to-batch variation dwarfs what 4 Adam updates move.
# An update left out, doubled or of the wrong sign reads 0.2 to 2;
# 2^-6 admits bf16-level gradient differences, which Adam passes through
# at about their own relative size (its step is scale-free in the
# gradient), plus the odd sign flip of a near-zero gradient.
TRAIN_PARAM_RTOL = 2.0 ** -6


def qwen_cut(n_layers: int):
    """qwen2.5-32b at its published widths, ``n_layers`` deep, with the
    chip's 1/8 share of the vocabulary."""
    full = configs.get_config("qwen2.5-32b")
    return dataclasses.replace(
        full, name=f"qwen2.5-32b-{n_layers}l-v1of{VOCAB_SHARE}",
        n_layers=n_layers, vocab_size=full.vocab_size // VOCAB_SHARE)


def _log(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def _prompts(rng, n: int, lens: tuple[int, int], vocab: int) -> list:
    lo, hi = lens
    return [rng.integers(0, vocab, int(rng.integers(lo, hi + 1)),
                         dtype=np.int32) for _ in range(n)]


def _engine(cfg, params, *, batch: int, max_len: int, block_size: int,
            **kw) -> ServeEngine:
    # one spare block per slot beyond its peak, so KV-aware admission
    # takes every request in the first tick
    per_slot = -(-max_len // block_size) + 1
    return ServeEngine(cfg, params, batch=batch, max_len=max_len,
                       backend="pim", paged=True, kv_block_size=block_size,
                       kv_blocks=1 + batch * per_slot, prefill="batch",
                       attn_kernel=True, expand_scans=True, **kw)


def _next_decode_args(eng: ServeEngine) -> tuple:
    """The decode input the engine's next tick feeds, read from its
    public state after the first tick: each slot's last sampled token at
    its position, the slots' block tables, and the cache."""
    reqs = eng.slots
    assert all(r is not None and len(r.out) == 1 for r in reqs), (
        "every request must be admitted and have sampled its first token")
    tokens = jnp.asarray([r.out[-1] for r in reqs], jnp.int32)
    pos = jnp.asarray([len(r.prompt) + len(r.out) - 1 for r in reqs],
                      jnp.int32)
    return eng.params, eng.cache, tokens, eng.kv.device_table(), pos


def _rel_dev(got, want) -> float:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    assert got.shape == want.shape and np.isfinite(got).all(), got.shape
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def _has_paged_kernel(hlo: str) -> bool:
    return any("tpu_custom_call" in line and "paged_decode_attention" in line
               for line in hlo.splitlines())


def serve_phase(cfg, *, seed: int = 0, batch: int = 8,
                prompt_lens: tuple[int, int] = (64, 128),
                new_tokens: int = 16, block_size: int = 8) -> dict:
    """Serve ``batch`` requests through the pim engine and compare one
    decode step's logits with the XLA reference. Returns timings, the
    deviation and the decode program's compiled text."""
    model = build_model(cfg)
    t0 = time.perf_counter()
    params = jax.block_until_ready(
        jax.jit(model.init)(jax.random.PRNGKey(seed)))
    init_s = time.perf_counter() - t0
    prompts = _prompts(np.random.default_rng(seed), batch, prompt_lens,
                       cfg.vocab_size)
    max_len = prompt_lens[1] + new_tokens

    t0 = time.perf_counter()
    eng = _engine(cfg, params, batch=batch, max_len=max_len,
                  block_size=block_size)
    build_s = time.perf_counter() - t0

    # compile the decode program before the first tick: set-up time kept
    # apart from serving, and its text shows which kernels compiled
    probe = (eng.params, eng.cache, jnp.zeros(batch, jnp.int32),
             eng.kv.device_table(), jnp.zeros(batch, jnp.int32))
    t0 = time.perf_counter()
    compiled = eng.pim_program.jitted.lower(*probe).compile()
    compile_s = time.perf_counter() - t0
    mem = compiled.memory_analysis()

    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p, max_tokens=new_tokens))
    # admission prefills every prompt (one compile per padded-length
    # bucket) and the tick decodes each final prompt token
    t0 = time.perf_counter()
    eng.tick_once()
    first_tick_s = time.perf_counter() - t0

    args = _next_decode_args(eng)
    got = eng.pim_program(*args)[0]
    with jax.default_matmul_precision("highest"):
        want = jax.jit(model.decode_step_paged)(*args)[0]
    dev = _rel_dev(got, want)
    argmax_agree = int(np.sum(np.argmax(np.asarray(got), -1)
                              == np.argmax(np.asarray(want), -1)))

    t0 = time.perf_counter()
    done = eng.run()
    run_s = time.perf_counter() - t0
    assert len(done) == batch, [r.rid for r in done]
    for r in done:
        assert len(r.out) == new_tokens, (r.rid, r.out)
        assert all(0 <= t < cfg.vocab_size for t in r.out), r.out
    return {
        "config": cfg.name, "layers": cfg.n_layers,
        "vocab": cfg.vocab_size, "batch": batch,
        "prompt_tokens": [len(p) for p in prompts],
        "new_tokens": new_tokens,
        "init_s": init_s, "build_s": build_s, "compile_s": compile_s,
        "first_tick_s": first_tick_s, "run_s": run_s,
        "run_ticks": new_tokens - 1,
        "decode_temp_bytes": getattr(mem, "temp_size_in_bytes", None),
        "decode_argument_bytes": getattr(mem, "argument_size_in_bytes",
                                         None),
        "logits_rel_dev": dev, "logits_tol": SERVE_LOGITS_TOL,
        "argmax_agree": argmax_agree,
        "placed_blocks": eng.pim_program.placed_blocks,
        "kernel_launches": eng.pim_program.kernel_launches,
        "hlo": compiled.as_text(),
    }


def train_phase(*, seed: int = 0, steps: int = 5, batch: int = 32) -> dict:
    """LeNet-5 for ``steps`` steps on the pim and the jit backends from
    one seed, each with a fresh checkpoint directory. Returns both loss
    curves, the deviation of the losses and of the parameters' changes,
    and the pim step's compiled text."""
    opt = make_optimizer("adamw", lr=2e-3)
    ds = DigitsDataset(batch_size=batch, seed=seed)

    def init_state():
        p = lenet.init_lenet(jax.random.PRNGKey(seed), LENET)
        return p, opt.init(p)

    def train_step(params, opt_state, batch):
        imgs, labels = batch
        loss, grads = jax.value_and_grad(lenet.lenet_loss)(
            params, jnp.asarray(imgs), jnp.asarray(labels))
        params, opt_state = opt.update(grads, opt_state, params)
        return params, opt_state, loss

    out: dict = {"steps": steps, "batch": batch}
    trained = {}
    with tempfile.TemporaryDirectory() as tmp:
        for backend in ("pim", "jit"):
            t0 = time.perf_counter()
            tr = Trainer(
                TrainerConfig(total_steps=steps,
                              ckpt_dir=os.path.join(tmp, backend),
                              async_ckpt=False),
                train_step=train_step, init_state=init_state,
                batch_fn=ds.batch, backend=backend)
            assert tr.resumed is False, f"{backend} trainer resumed"
            if backend == "pim":
                compiled = tr.pim_program.jitted.lower(
                    tr.params, tr.opt_state, ds.batch(0)).compile()
                out["hlo"] = compiled.as_text()
            out[f"{backend}_setup_s"] = time.perf_counter() - t0
            t0 = time.perf_counter()
            res = tr.run()
            out[f"{backend}_run_s"] = time.perf_counter() - t0
            assert res["resumed"] is False and res["start_step"] == 0, res
            out[f"{backend}_losses"] = res["losses"]
            trained[backend] = tr.params
    pim, ref = np.asarray(out["pim_losses"]), np.asarray(out["jit_losses"])
    assert len(pim) == len(ref) == steps and np.isfinite(pim).all(), pim
    out["loss_rel_dev"] = float(np.max(np.abs(pim - ref) / np.abs(ref)))
    out["loss_rtol"] = TRAIN_LOSS_RTOL

    devs = {}
    p0 = init_state()[0]
    flat = (jax.tree_util.tree_flatten_with_path(p)[0]
            for p in (p0, trained["pim"], trained["jit"]))
    for (path, x0), (_, xp), (_, xj) in zip(*flat, strict=True):
        name = jax.tree_util.keystr(path)
        x0, xp, xj = (np.asarray(x, np.float64) for x in (x0, xp, xj))
        dj = np.linalg.norm(xj - x0)
        assert dj > 0 and np.isfinite(xp).all(), (name, dj)
        devs[name] = float(np.linalg.norm(xp - xj) / dj)
    out["param_rel_dev"] = max(devs.values())
    out["param_rel_dev_by_leaf"] = devs
    out["param_rtol"] = TRAIN_PARAM_RTOL
    return out


def pipeline_phase(cfg, devices, *, seed: int = 0, batch: int = 2,
                   prompt_len: int = 64, new_tokens: int = 4,
                   block_size: int = 8) -> dict:
    """The device-pinned pipeline: ``len(devices)`` partitions, stage i
    pinned to ``devices[i]``, against the same partitioned engine
    unpinned. Returns the logits deviation and the devices each stage's
    outputs sit on."""
    model = build_model(cfg)
    params = jax.block_until_ready(
        jax.jit(model.init)(jax.random.PRNGKey(seed)))
    prompts = _prompts(np.random.default_rng(seed), batch,
                       (prompt_len, prompt_len), cfg.vocab_size)
    kw = dict(batch=batch, max_len=prompt_len + new_tokens,
              block_size=block_size, partitions=len(devices))
    t0 = time.perf_counter()
    plain = _engine(cfg, params, **kw)
    pinned = _engine(cfg, params, pim_compile={"devices": list(devices)},
                     **kw)
    build_s = time.perf_counter() - t0

    for i, p in enumerate(prompts):
        plain.submit(Request(rid=i, prompt=p, max_tokens=new_tokens))
    t0 = time.perf_counter()
    plain.tick_once()
    args = _next_decode_args(plain)
    want = plain.pim_program(*args)[0]
    prog = pinned.pim_program
    got = prog.run_async(*args)[0]
    stage_outs = prog.run_stages_async(*args)
    compare_s = time.perf_counter() - t0
    dev = _rel_dev(got, want)
    stage_devices = []
    for st, outs in zip(prog.stages, stage_outs):
        on = {str(d) for x in jax.tree.leaves(outs) for d in x.devices()}
        assert on == {str(st.device)}, (st.idx, on, st.device)
        stage_devices.append(sorted(on)[0])
    assert len(set(stage_devices)) == len(devices), stage_devices

    # the pinned engine serves end to end through its async chain
    for i, p in enumerate(prompts):
        pinned.submit(Request(rid=i, prompt=p, max_tokens=new_tokens))
    t0 = time.perf_counter()
    done = pinned.run()
    run_s = time.perf_counter() - t0
    assert len(done) == batch and all(len(r.out) == new_tokens
                                      for r in done), done
    return {
        "config": cfg.name, "layers": cfg.n_layers,
        "partitions": len(prog.stages), "build_s": build_s,
        "compare_s": compare_s, "pinned_run_s": run_s,
        "logits_rel_dev": dev, "logits_tol": SERVE_LOGITS_TOL,
        "stage_devices": stage_devices,
    }


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the device-pinned pipeline on 4 chips")
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devices[0].platform}",
              file=sys.stderr)
        return 1
    # on the TPU no kernel of the main path may run in the interpreter
    assert resolve_interpret(None) is False

    from repro.launch.cache import use_compile_cache
    _log("setup", compile_cache=use_compile_cache(ROOT))

    if args.four_chips:
        if len(devices) < 4:
            print(f"chip_smoke: --four-chips needs 4 devices, JAX found "
                  f"{len(devices)}", file=sys.stderr)
            return 1
        res = pipeline_phase(qwen_cut(4), devices[:4])
        _log("pipeline", **res)
        assert res["logits_rel_dev"] <= SERVE_LOGITS_TOL, res
        count = 4
    else:
        res = serve_phase(qwen_cut(2))
        hlo = res.pop("hlo")
        _log("serve", **res)
        assert "tpu_custom_call" in hlo and _has_paged_kernel(hlo), (
            "the pim decode program holds no compiled paged kernel")
        assert res["logits_rel_dev"] <= SERVE_LOGITS_TOL, res
        res = train_phase()
        hlo = res.pop("hlo")
        _log("train", **res)
        assert "tpu_custom_call" in hlo, (
            "the pim train step holds no compiled kernel")
        assert res["loss_rel_dev"] <= TRAIN_LOSS_RTOL, res
        assert res["param_rel_dev"] <= TRAIN_PARAM_RTOL, res
        count = len(devices)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
