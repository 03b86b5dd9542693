"""Train a small LM (reduced config of any assigned arch) on the synthetic
Markov token stream — exercises the full framework path: config -> model ->
sharding rules -> fused-xent train step -> trainer with checkpoints.

    PYTHONPATH=src python examples/lm_train.py --arch llama3-8b --steps 60
"""

import argparse
import tempfile
import dataclasses

import jax

from repro import configs
from repro.data import TokenStream
from repro.launch import steps as steps_mod
from repro.models.transformer import build_model
from repro.optim import make_optimizer
from repro.train import Trainer, TrainerConfig


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3-8b",
                    choices=list(configs.ARCH_IDS))
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--ckpt", default=None,
                    help="checkpoint dir to resume from and save to "
                         "(default: a fresh temporary dir)")
    args = ap.parse_args()

    cfg = configs.get_smoke_config(args.arch)
    if cfg.input_embed_stub:
        raise SystemExit("pick a token arch for this example "
                         "(audio/vlm need the frontend stub driver)")
    model = build_model(cfg)
    opt = make_optimizer("adamw", lr=3e-3, state_dtype=cfg.opt_state_dtype)
    ts = TokenStream(vocab_size=cfg.vocab_size, seq_len=args.seq,
                     batch_size=args.batch, seed=0)
    step = steps_mod.make_train_step(cfg, optimizer_name="adamw", lr=3e-3)

    def init_state():
        p = model.init(jax.random.PRNGKey(0))
        return p, opt.init(p)

    tr = Trainer(TrainerConfig(total_steps=args.steps, ckpt_every=25,
                               ckpt_dir=args.ckpt or tempfile.mkdtemp()),
                 train_step=step, init_state=init_state, batch_fn=ts.batch)
    res = tr.run()
    import math
    uniform = math.log(cfg.vocab_size)
    print(f"{args.arch}: loss {res['losses'][0]:.3f} -> "
          f"{res['final_loss']:.3f} (uniform={uniform:.3f})")
    assert res["final_loss"] < res["losses"][0]


if __name__ == "__main__":
    main()
