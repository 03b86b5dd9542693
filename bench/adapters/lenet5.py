"""How the benchmark drives the program's trainer for LeNet-5: the
program's model, optimizer and digits, the benchmark's weights,
``Trainer(backend="pim")``, fed from a training set held in memory."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.data.pipeline import make_digits
from repro.models import lenet
from repro.optim import make_optimizer
from repro.train import Trainer, TrainerConfig

# no checkpoint inside a run: saves are a mix of their own
NEVER = 10 ** 12


def trainer(cfg: dict, mix: dict, params, seed: int, ckpt_dir: str) -> Trainer:
    o = cfg["optimizer"]
    opt = make_optimizer(o["name"], lr=o["lr"], b1=o["b1"], b2=o["b2"],
                         eps=o["eps"], weight_decay=o["weight_decay"])

    def init_state():
        return params, opt.init(params)

    def train_step(p, state, batch):
        imgs, labels = batch
        loss, grads = jax.value_and_grad(lenet.lenet_loss)(
            p, jnp.asarray(imgs), jnp.asarray(labels))
        p, state = opt.update(grads, state, p)
        return p, state, loss

    return Trainer(TrainerConfig(total_steps=NEVER, ckpt_dir=ckpt_dir,
                                 ckpt_every=NEVER, async_ckpt=False),
                   train_step=train_step, init_state=init_state,
                   batch_fn=InMemory(mix, seed).batch, backend="pim")


class InMemory:
    """A training set rendered once from the seed with the program's
    digit generator and kept in host memory, as a loader keeps MNIST;
    each epoch visits it in a new order drawn from the seed."""

    def __init__(self, mix: dict, seed: int):
        self.b = mix["batch"]
        self.seed = seed
        self.imgs, self.labels = make_digits(mix["dataset_images"],
                                             seed=seed)
        self.per_epoch = len(self.labels) // self.b
        self._epoch, self._order = -1, None

    def batch(self, step: int) -> tuple[np.ndarray, np.ndarray]:
        epoch, i = divmod(step, self.per_epoch)
        if epoch != self._epoch:
            rng = np.random.default_rng([self.seed, epoch])
            self._epoch, self._order = epoch, rng.permutation(
                len(self.labels))
        idx = self._order[i * self.b:(i + 1) * self.b]
        return self.imgs[idx], self.labels[idx]


def first_moment(opt_state) -> dict:
    """Adam's first moment after one step, ``(1 - b1) * g``."""
    return opt_state["m"]
