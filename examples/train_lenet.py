"""End-to-end driver of the paper's experiment (§4): train the 21.7k-param
LeNet on the procedural digits dataset (MNIST surrogate — DESIGN.md §2),
with fault-tolerant checkpointing, then report BOTH the achieved accuracy
and the PIM accelerator cost of the training run (Fig. 6 pipeline).

    PYTHONPATH=src python examples/train_lenet.py [--steps 300]
"""

import argparse
import tempfile

import jax
import jax.numpy as jnp

from repro.configs.lenet5 import CONFIG
from repro.core import accelerator
from repro.data import DigitsDataset
from repro.models import lenet
from repro.optim import make_optimizer
from repro.train import Trainer, TrainerConfig, trainer as trainer_mod


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--ckpt", default=None,
                    help="checkpoint dir to resume from and save to "
                         "(default: a fresh temporary dir)")
    args = ap.parse_args()

    opt = make_optimizer("adamw", lr=2e-3)
    ds = DigitsDataset(batch_size=args.batch, seed=0)

    def init_state():
        p = lenet.init_lenet(jax.random.PRNGKey(0), CONFIG)
        return p, opt.init(p)

    def train_step(params, opt_state, batch):
        imgs, labels = batch
        loss, grads = jax.value_and_grad(lenet.lenet_loss)(
            params, jnp.asarray(imgs), jnp.asarray(labels))
        params, opt_state = opt.update(grads, opt_state, params)
        return params, opt_state, loss

    tr = Trainer(TrainerConfig(total_steps=args.steps, ckpt_every=50,
                               ckpt_dir=args.ckpt or tempfile.mkdtemp()),
                 train_step=train_step, init_state=init_state,
                 batch_fn=ds.batch)
    res = tr.run()
    print(f"resumed={res['resumed']} start={res['start_step']} "
          f"final_loss={res['final_loss']:.4f}")

    imgs, labels = ds.eval_set(2000)
    acc = trainer_mod.eval_accuracy(
        jax.jit(lenet.lenet_apply), tr.params, imgs, labels)
    print(f"eval accuracy: {acc*100:.2f}%  "
          "(paper reports 97.08% on true MNIST)")

    # PIM accelerator cost of this training run (the Fig. 6 pipeline)
    layers = accelerator.lenet_layers()
    for tech in ("proposed", "floatpim"):
        rep = accelerator.PIMAccelerator(tech).train(
            layers, batch=args.batch, steps=args.steps)
        print(f"[{tech:9s}] energy={rep.energy_j:.3e} J  "
              f"latency={rep.latency_s:.3f} s  "
              f"area={rep.area_m2*1e6:.3f} mm^2")


if __name__ == "__main__":
    main()
