"""Fault-tolerant training loop.

Composes the substrates: step functions (``repro.launch.steps``), optimizer,
stateless-resumable data pipeline, checkpoint manager, and the
heartbeat/straggler monitors. Properties exercised by the integration
tests:

  * **auto-resume**: on construction the trainer restores the newest
    complete checkpoint and continues from that step; because the data
    pipeline is a pure function of the step counter, the resumed run sees
    exactly the batches the uninterrupted run would have;
  * **crash-safety**: checkpoints are atomic (temp+rename) and written
    asynchronously every ``ckpt_every`` steps;
  * **failure injection**: ``fail_at_step`` simulates a mid-run node death
    (raises) — the test restarts the trainer and verifies bit-identical
    convergence with an uninterrupted run;
  * **straggler events** recorded via ``StragglerPolicy``.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable

import jax
import numpy as np

from repro import obs
from repro.checkpoint import CheckpointManager
from repro.train.monitor import HeartbeatMonitor, StragglerPolicy


@dataclasses.dataclass
class TrainerConfig:
    total_steps: int
    # no default: the trainer resumes from whatever this directory holds,
    # so a shared fallback path would silently continue an unrelated run
    ckpt_dir: str
    ckpt_every: int = 50
    keep: int = 3
    async_ckpt: bool = True
    log_every: int = 10
    fail_at_step: int | None = None    # failure injection (tests)


class Trainer:
    def __init__(self, cfg: TrainerConfig, *, train_step: Callable,
                 init_state: Callable[[], tuple[Any, Any]],
                 batch_fn: Callable[[int], Any],
                 jit_kwargs: dict | None = None,
                 backend: str = "jit", pim_tech: str = "proposed",
                 weight_dtype: str = "fp32", act_dtype: str = "fp32",
                 microbatches: int = 1, partitions: int = 1,
                 loss_fn: Callable | None = None, optimizer=None,
                 pim_compile: dict | None = None):
        """``train_step(params, opt_state, batch) -> (params, opt, loss)``;
        ``init_state()`` builds fresh (params, opt_state);
        ``batch_fn(step)`` is the stateless data pipeline.

        ``backend="jit"`` runs the step under plain ``jax.jit``;
        ``backend="pim"`` maps the full loss+grad step onto the PIM
        hierarchy and runs the *compiled schedule* — every placed matmul
        executes as blocked ``pim_matmul`` calls per resident weight
        block (see ``repro.mapper.compile``). The placed schedule is
        exposed as ``self.pim_program.schedule``.

        ``microbatches=M`` / ``partitions=K`` (pim backend only) run the
        *partitioned pipeline plan*: the loss graph is cut into K pipeline
        partitions compiled one program each, the batch is split into M
        equal microbatches, and each step streams them through the stage
        programs with GPipe fill-drain, differentiating per stage
        (``repro.parallel.pipeline.gpipe_value_and_grad``) and applying
        one optimizer update on the microbatch-mean gradients. Requires
        ``loss_fn(params, *batch) -> scalar mean loss`` and an
        ``optimizer`` with ``update(grads, opt_state, params)`` (the
        opaque ``train_step`` cannot be split); losses match the jit
        backend to fp32 tolerance because a mean over equal microbatch
        means is the full-batch mean.

        ``act_dtype`` (pim backend only) prices inter-stage activation
        transfers on the modeled NoC at the reduced width from
        ``core.quant`` — compute stays fp32, only ``t_xfer`` shrinks.

        ``weight_dtype`` (pim backend only) stores placed weights on a
        reduced-precision grid (``int8`` / ``fp8_e4m3`` / ``fp8_e5m2`` /
        ``fp16``): denser placement, more throughput replicas, and
        dequantize-on-load matmuls with fp32 accumulation and
        straight-through gradients (see ``repro.core.quant``).

        ``pim_compile`` forwards knobs to the schedule compiler (e.g.
        ``{"group": False, "fuse": False}`` for the legacy
        one-launch-per-block program — grouped launches model the
        hardware but serialize under CPU interpret emulation)."""
        self.cfg = cfg
        self.batch_fn = batch_fn
        self.backend = backend
        self.microbatches = microbatches
        self.partitions = partitions
        self.ckpt = CheckpointManager(cfg.ckpt_dir, keep=cfg.keep,
                                      async_save=cfg.async_ckpt)
        self.straggler = StragglerPolicy()
        self.heartbeat = HeartbeatMonitor()
        self.pim_program = None
        if microbatches < 1 or partitions < 1:
            raise ValueError("microbatches and partitions must be >= 1")
        pipelined = microbatches > 1 or partitions > 1
        if pipelined and backend != "pim":
            raise ValueError(
                "microbatches/partitions require backend='pim' (the jit "
                "backend has no partitioned plan to pipeline)")

        params, opt_state = init_state()
        if backend != "jit" and jit_kwargs:
            raise ValueError(
                "jit_kwargs only apply to backend='jit'; the pim "
                "backend jits the compiled schedule itself")
        if backend == "jit" and pim_compile:
            raise ValueError("pim_compile only applies to backend='pim'")
        if backend != "pim" and weight_dtype != "fp32":
            raise ValueError(
                "weight_dtype only applies to backend='pim' (the jit "
                "backend has no placed weight grid to quantize)")
        if backend != "pim" and act_dtype != "fp32":
            raise ValueError(
                "act_dtype only applies to backend='pim' (the jit "
                "backend has no modeled NoC to narrow transfers on)")
        self._pim_compile = dict(pim_compile or {})
        self.weight_dtype = weight_dtype
        self.act_dtype = act_dtype
        if backend == "jit":
            self._step_fn = jax.jit(train_step, **(jit_kwargs or {}))
        elif backend == "pim" and not pipelined:
            from repro import mapper
            sched = mapper.build_schedule(train_step, params, opt_state,
                                          batch_fn(0), tech=pim_tech,
                                          weight_dtype=weight_dtype,
                                          act_dtype=act_dtype)
            # use_cache=False: the global program cache keys on fn
            # identity, and this per-instance train_step closure would
            # never hit but would be pinned (params and all) forever
            self.pim_program = mapper.compile_schedule(
                sched, use_cache=False, **self._pim_compile)
            self._step_fn = self.pim_program
        elif backend == "pim":
            self._step_fn = self._build_pipelined_step(
                params, batch_fn(0), loss_fn, optimizer, pim_tech,
                weight_dtype, act_dtype)
        else:
            raise ValueError(f"backend must be 'jit' or 'pim', "
                             f"got {backend!r}")
        restored, step = self.ckpt.restore({"params": params,
                                            "opt": opt_state})
        if restored is not None:
            params, opt_state = restored["params"], restored["opt"]
            self.start_step = step + 1
            self.resumed = True
        else:
            self.start_step = 0
            self.resumed = False
        self.params = params
        self.opt_state = opt_state
        self.losses: list[float] = []

    def _build_pipelined_step(self, params, batch0, loss_fn, optimizer,
                              pim_tech: str,
                              weight_dtype: str = "fp32",
                              act_dtype: str = "fp32") -> Callable:
        """Compile the partitioned microbatch-pipeline step (see
        ``__init__``). Traces ``loss_fn`` at microbatch shape, cuts it
        into ``self.partitions`` stage programs, and returns a jitted
        ``step(params, opt_state, batch)`` that GPipe-streams the
        microbatches and applies one update on the mean gradients."""
        if loss_fn is None or optimizer is None:
            raise ValueError(
                "microbatches/partitions need loss_fn and optimizer: an "
                "opaque train_step cannot be cut into pipeline stages")
        from repro import mapper
        from repro.parallel import pipeline as pipe_mod

        n_micro = self.microbatches
        leaves = jax.tree.leaves(batch0)
        if not leaves:
            raise ValueError("batch_fn(0) returned an empty batch")
        batch_dim = int(np.shape(leaves[0])[0])
        if any(int(np.shape(x)[0]) != batch_dim for x in leaves):
            raise ValueError("all batch leaves must share the leading "
                             "(batch) axis to be microbatched")
        if batch_dim % n_micro:
            raise ValueError(f"batch size {batch_dim} is not divisible "
                             f"into {n_micro} microbatches")
        mb = batch_dim // n_micro

        def slice_mb(batch, m):
            return jax.tree.map(lambda a: a[m * mb:(m + 1) * mb], batch)

        mb_abstract = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct((mb,) + np.shape(a)[1:],
                                           np.asarray(a).dtype),
            batch0)
        sched = mapper.build_schedule(
            loss_fn, mapper.abstract_like(params), *mb_abstract,
            tech=pim_tech, weight_dtype=weight_dtype,
            act_dtype=act_dtype, partitions=self.partitions)
        # use_cache=False for the same pinning reason as the whole-step
        # path: per-instance params would live in the global cache forever
        prog = mapper.compile_partitioned(sched, use_cache=False,
                                          **self._pim_compile)
        self.pim_program = prog
        loss_ref = prog.out_refs[0]
        n_param_leaves = len(jax.tree.leaves(params))
        params_treedef = jax.tree.structure(params)

        def step(params, opt_state, batch):
            flat_per_mb = [prog.flatten_args(params, *slice_mb(batch, m))
                           for m in range(n_micro)]
            loss, grad_flat = pipe_mod.gpipe_value_and_grad(
                prog.stages, loss_ref, flat_per_mb,
                list(range(n_param_leaves)))
            grads = jax.tree.unflatten(params_treedef, grad_flat)
            params, opt_state = optimizer.update(grads, opt_state, params)
            return params, opt_state, loss

        if any(st.device is not None for st in prog.stages):
            # device-pinned stages: keep the step eager so the GPipe
            # driver's per-stage device_put routing actually happens —
            # wrapping in jax.jit would trace the whole grid into one
            # single-device program and erase the pinning
            return step
        return jax.jit(step)

    def run(self) -> dict:
        cfg = self.cfg
        m = obs.metrics()
        step = self.start_step
        first_step = True
        while step < cfg.total_steps:
            if cfg.fail_at_step is not None and step == cfg.fail_at_step:
                raise RuntimeError(f"injected node failure at step {step}")
            with obs.span("train:step", lane="train",
                          lazy=lambda: {"step": step}):
                t0 = time.monotonic()
                with obs.span("train:batch", lane="train"):
                    batch = self.batch_fn(step)
                with obs.span("train:dispatch", lane="train"), \
                        obs.program("train.step"):
                    self.params, self.opt_state, loss = self._step_fn(
                        self.params, self.opt_state, batch)
                with obs.span("train:sync", lane="train"):
                    loss = float(loss)  # device sync: dt is true step time
                dt = time.monotonic() - t0
                m.histogram("train.step_wall_s").observe(dt)
                if first_step:
                    # the resumed-run first step pays trace + compile;
                    # record it apart so the steady-state histogram
                    # stays clean
                    m.gauge("train.first_step_wall_s").set(dt)
                    first_step = False
                m.counter("train.steps").inc()
                self.heartbeat.beat("host0")
                self.straggler.observe(step, dt)
                self.losses.append(loss)
                if step % cfg.ckpt_every == 0 and step > self.start_step:
                    with obs.span("train:ckpt", lane="train"):
                        self.ckpt.save(step, {"params": self.params,
                                              "opt": self.opt_state})
            step += 1
        # final checkpoint
        self.ckpt.save(cfg.total_steps - 1,
                       {"params": self.params, "opt": self.opt_state})
        self.ckpt.wait()
        return {
            "final_loss": self.losses[-1] if self.losses else float("nan"),
            "losses": self.losses,
            "resumed": self.resumed,
            "start_step": self.start_step,
            "straggler_events": self.straggler.events,
        }


def eval_accuracy(apply_fn, params, images: np.ndarray,
                  labels: np.ndarray, batch: int = 500) -> float:
    correct = 0
    for i in range(0, len(images), batch):
        logits = apply_fn(params, images[i:i + batch])
        correct += int((np.argmax(np.asarray(logits), -1)
                        == labels[i:i + batch]).sum())
    return correct / len(images)
