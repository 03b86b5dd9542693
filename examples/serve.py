"""Batched autoregressive serving demo: prefill a prompt batch, then decode
tokens through the KV cache / recurrent states with greedy sampling.

    PYTHONPATH=src python examples/serve.py --arch xlstm-350m --tokens 16
"""

import argparse
import time

import jax
import jax.numpy as jnp

from repro import configs
from repro.models.transformer import build_model


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3-8b",
                    choices=list(configs.ARCH_IDS))
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--tokens", type=int, default=16)
    ap.add_argument("--smoke", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="reduced config (--no-smoke: published widths)")
    args = ap.parse_args()

    cfg = (configs.get_smoke_config(args.arch) if args.smoke
           else configs.get_config(args.arch))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    max_len = args.prompt_len + args.tokens
    cache = model.init_cache(args.batch, max_len)
    prompt = jax.random.randint(jax.random.PRNGKey(1),
                                (args.batch, args.prompt_len), 0,
                                cfg.vocab_size)

    decode = jax.jit(model.decode_step)
    # prefill via decode steps (simple path; prefill_step covers the bulk)
    tok = prompt[:, 0]
    for t in range(args.prompt_len):
        logits, cache = decode(params, cache, prompt[:, t], jnp.int32(t))
    out = []
    t0 = time.perf_counter()
    tok = jnp.argmax(logits, -1)
    for t in range(args.prompt_len, max_len):
        out.append(tok)
        logits, cache = decode(params, cache, tok, jnp.int32(t))
        tok = jnp.argmax(logits, -1)
    dt = time.perf_counter() - t0
    gen = jnp.stack(out, 1)
    print(f"{args.arch}: generated {gen.shape} tokens in {dt:.2f}s "
          f"({args.batch*args.tokens/dt:.1f} tok/s on "
          f"{jax.devices()[0].platform}, {cfg.name})")
    print(gen[0][:12])


if __name__ == "__main__":
    main()
