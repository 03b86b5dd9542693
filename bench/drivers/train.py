"""Training cells: the program's ``Trainer`` run back to back through
``Trainer.run``, timed by the host clock, checked against the plain
reference.

Set-up builds one trainer from the benchmark's weights and drives it
through its first three steps with the same call and feed the window
uses; those steps compile or load the step program. The window then
continues the same trainer for ``--seconds``. The reference follows the
first three steps from the same weights and batches; the run compares
each step's loss, the first gradient as the optimizer received it (read
back from Adam's first moment) and the parameters' change over the three
steps.
"""

from __future__ import annotations

import gc
import tempfile
import time

import jax
import numpy as np

import harness
import tracing

CHECK_STEPS = 3


class Run:
    def __init__(self, cell: harness.Cell, seed: int, window_s: float,
                 clock: harness.Clock, counter: harness.CompileCounter):
        self.cell, self.seed, self.window_s = cell, seed, window_s
        self.clock, self.counter = clock, counter
        self.cfg, self.mix = cell.config, cell.traffic
        self.ad = harness.load_module("adapters", self.cfg["family"])
        self.ref = harness.load_module("models", self.cfg["family"])
        self.batches = []          # the first steps' feed, for the reference
        self.moment = None         # Adam's first moment after step 1
        self.after = None          # parameters after CHECK_STEPS steps
        self.done_at = []          # completion time of each window step
        self.t_window = None
        self.setup_s = None
        self.rec = None

    def _feed(self, step: int):
        """The trainer's batch function: the program's pipeline, with the
        benchmark's bookkeeping at each step boundary."""
        now = time.monotonic()
        tr = self.trainer
        if step == 1:
            self.moment = jax.tree.map(np.asarray,
                                       self.ad.first_moment(tr.opt_state))
        if step == CHECK_STEPS:
            self.after = jax.tree.map(np.asarray, tr.params)
            self.setup_s = self.clock.now()
            self.t_window = now = time.monotonic()
            self.counter.armed = True
        elif step > CHECK_STEPS:
            self.done_at.append(now)
            if now >= self.t_window + self.window_s:
                tr.cfg.total_steps = step + 1     # this step is the last
        if self.rec is not None and self.t_window is not None:
            self.rec.poll(now - self.t_window)
        batch = self.data(step)
        if step < CHECK_STEPS:
            self.batches.append(jax.tree.map(np.array, batch))
        return batch

    def go(self, trace: dict | None) -> None:
        self.p0 = self.ref.make_params(self.cfg, self.seed)
        p0_host = jax.tree.map(np.asarray, self.p0)
        with tempfile.TemporaryDirectory() as ckpt:
            self.trainer = self.ad.trainer(self.cfg, self.mix, self.p0,
                                           self.seed, ckpt)
            self.data = self.trainer.batch_fn
            self.trainer.batch_fn = self._feed
            if trace is not None:
                self.rec = tracing.TrainRecorder(self.trainer, trace)
            self.trainer.run()
            self.counter.armed = False
            self.losses = self.trainer.losses[:CHECK_STEPS]
            self.trainer = None
        self.p0 = p0_host

    def images_per_s(self) -> float:
        t0 = self.t_window
        n = sum(1 for t in self.done_at if t0 <= t < t0 + self.window_s)
        return n * self.mix["batch"] / self.window_s

    def check(self) -> list[harness.Check]:
        p0 = jax.tree.map(np.asarray, self.p0)
        want = self.ref.run(self.cfg, p0, self.batches,
                            precision=self.cfg["matmul_precision"])
        b1 = self.cfg["optimizer"]["b1"]
        grad = jax.tree.map(lambda m: m / (1 - b1), self.moment)
        loss_gap = max(abs(a - b) / abs(b) for a, b in
                       zip(self.losses, want["losses"]))
        d_prog = jax.tree.map(lambda a, b: a - b, self.after, p0)
        d_ref = jax.tree.map(lambda a, b: np.asarray(a) - b,
                             want["params"], p0)
        return [harness.Check("loss_rel_gap", loss_gap,
                              self.mix["check"]["loss_rel_gap"]),
                harness.Check("grad_norm_rel_gap",
                              norm_gap(grad, want["grad"], want["grad"]),
                              self.mix["check"]["grad_norm_rel_gap"]),
                harness.Check("update_norm_rel_gap",
                              norm_gap(d_prog, d_ref, want["grad"]),
                              self.mix["check"]["update_norm_rel_gap"])]


def norm_gap(got, want, ref_grad) -> float:
    """The worst leaf's gap between the program's norm and the
    reference's, over the larger of that leaf's reference norm and the
    median leaf's. Leaves whose reference gradient is under a thousandth
    of the median leaf's move by round-off alone and are left out."""
    g = [float(np.linalg.norm(np.asarray(x, np.float64)))
         for x in jax.tree.leaves(ref_grad)]
    a = [float(np.linalg.norm(np.asarray(x, np.float64)))
         for x in jax.tree.leaves(got)]
    b = [float(np.linalg.norm(np.asarray(x, np.float64)))
         for x in jax.tree.leaves(want)]
    med_g, med_b = float(np.median(g)), float(np.median(b))
    gaps = [abs(x - y) / max(y, med_b)
            for x, y, gn in zip(a, b, g) if gn >= 1e-3 * med_g]
    return max(gaps)


def run(cell: harness.Cell, args, clock: harness.Clock,
        counter: harness.CompileCounter, devs) -> bool:
    with jax.default_matmul_precision(cell.config["matmul_precision"]):
        r = Run(cell, args.seed, args.seconds, clock, counter)
        r.go(cell.traffic["trace"] if args.trace else None)
        device = harness.device_record(devs)
        summary = (tracing.summarize(r.rec.read(), devs)
                   if r.rec is not None else None)
        harness.log(steps_in_window=len(r.done_at),
                    compiles_in_window=counter.count, losses=r.losses)
        gc.collect()
        t0 = time.monotonic()
        checks = r.check()
        harness.log(check_s=time.monotonic() - t0)
    metrics = {"train_images_per_s": {"value": r.images_per_s(),
                                      "unit": "images/s"},
               "setup_s": {"value": r.setup_s, "unit": "s"}}
    breakdown = None
    if args.trace:
        metrics, breakdown = tracing.per_layer(cell, summary, device, devs)
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
    return harness.emit(checks=checks, attempted=len(r.done_at), failed=0,
                        metrics=metrics, device=device, breakdown=breakdown)
