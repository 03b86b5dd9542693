"""Training launcher: config -> mesh -> sharding rules -> fault-tolerant
trainer. On real hardware the production mesh spans pods; on this host it
runs reduced (smoke) configs on the local device mesh.

    PYTHONPATH=src python -m repro.launch.train --arch llama3-8b \
        --steps 50 [--no-smoke] [--model-axis 1]
"""

from __future__ import annotations

import argparse
import tempfile

import jax

from repro import configs
from repro.data import TokenStream
from repro.launch import steps as steps_mod
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models.transformer import build_model
from repro.optim import make_optimizer
from repro.parallel import sharding
from repro.train import Trainer, TrainerConfig


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3-8b",
                    choices=list(configs.ARCH_IDS))
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--smoke", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="reduced config (--no-smoke: published widths, "
                         "which need a TPU fleet)")
    ap.add_argument("--production-mesh", action="store_true")
    ap.add_argument("--model-axis", type=int, default=1)
    ap.add_argument("--ckpt", default=None,
                    help="checkpoint dir to resume from and save to "
                         "(default: a fresh temporary dir)")
    args = ap.parse_args()

    cfg = (configs.get_smoke_config(args.arch) if args.smoke
           else configs.get_config(args.arch))
    if cfg.input_embed_stub:
        raise SystemExit("audio/vlm archs need the frontend-stub driver")

    if args.production_mesh:
        mesh = make_production_mesh()
    else:
        mesh = make_host_mesh(model_axis=args.model_axis)
    rules = sharding.single_pod_rules(mesh, fsdp=cfg.fsdp)

    model = build_model(cfg)
    opt = make_optimizer("adamw", lr=args.lr,
                         state_dtype=cfg.opt_state_dtype)
    ts = TokenStream(vocab_size=cfg.vocab_size, seq_len=args.seq,
                     batch_size=args.batch, seed=0)
    step = steps_mod.make_train_step(cfg, optimizer_name="adamw",
                                     lr=args.lr)

    def init_state():
        params = model.init(jax.random.PRNGKey(0))
        if mesh.size > 1:
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P
            specs = sharding.param_specs(params, rules)
            params = jax.device_put(params, jax.tree.map(
                lambda sp: NamedSharding(mesh, sp), specs,
                is_leaf=lambda x: isinstance(x, P)))
        return params, opt.init(params)

    with mesh, sharding.use_rules(rules):
        tr = Trainer(TrainerConfig(total_steps=args.steps, ckpt_every=25,
                                   ckpt_dir=args.ckpt or tempfile.mkdtemp()),
                     train_step=step, init_state=init_state,
                     batch_fn=ts.batch)
        res = tr.run()
    print(f"{args.arch} on {mesh.shape}: loss {res['losses'][0]:.3f} -> "
          f"{res['final_loss']:.3f}  (resumed={res['resumed']}, "
          f"stragglers={len(res['straggler_events'])})")


if __name__ == "__main__":
    main()
