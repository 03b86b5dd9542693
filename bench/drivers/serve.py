"""Serving cells: the program's ``ServeEngine`` under an open or a closed
loop, timed by the host clock, checked against the plain reference.

Set-up makes the weights on the device from the seed, builds the engine
the configuration names, and warms up every program the mix reaches: the
decode program and one prefill program per padded prompt length. Then
the loop drives ``tick_once`` for the mix's pre-roll and the measured
window. Once the window has closed and the memory peak is read, the
program's state is freed and the reference draws the same weights again
and scores a sample of the finished requests.
"""

from __future__ import annotations

import gc
import time

import jax
import numpy as np

import harness
import traffic as traffic_mod
import tracing
from repro.serve import Request


class Run:
    """One serving run: the engine, its requests and what was measured."""

    def __init__(self, cell: harness.Cell, seed: int, window_s: float):
        self.cell, self.seed, self.window_s = cell, seed, window_s
        self.cfg, self.mix = cell.config, cell.traffic
        self.ad = harness.load_module("adapters", self.cfg["family"])
        self.ref = harness.load_module("models", self.cfg["family"])
        self.vocab = self.cfg["vocab_size"]
        self.reqs: list[Request] = []
        self.measured: list[Request] = []
        self.lateness: list[float] = []
        self.t_window = 0.0
        self.rec = None
        self.ticks: list[tuple[float, float, float]] = []
        self._sync_s = 0.0
        self.gc_pauses = GcPauses()

    # -- set-up -------------------------------------------------------------

    def setup(self) -> None:
        w = self.ref.make_weights(self.cfg, self.seed)
        params = jax.block_until_ready(self.ad.program_params(w))
        del w
        self.eng = self.ad.engine(self.cfg, self.mix, params)
        rng = traffic_mod.rng_for(self.seed, 1)
        # one request per prefill bucket that this run's prompts reach,
        # and the decode program: each compiles or loads here
        buckets = harness.prefill_buckets(self.mix, self.window_s)
        harness.log(prefill_buckets=len(buckets))
        for i, n in enumerate(buckets.values()):
            self.eng.submit(Request(rid=-1 - i, max_tokens=2, prompt=rng.integers(
                0, self.vocab, n, dtype=np.int32)))
        self.eng.run()
        self.eng.completed.clear()
        jax.block_until_ready(self.eng.cache)
        sample = self.eng.sample

        def timed_sample(logits):
            # the engine syncs here anyway (np.asarray of the sampled ids)
            t = time.monotonic()
            out = np.asarray(sample(logits))
            self._sync_s += time.monotonic() - t
            return out
        self.eng.sample = timed_sample

    # -- the loops ------------------------------------------------------------

    def _submit(self, item, rid: int, due: float) -> Request:
        r = Request(rid=rid, prompt=item.prompt, max_tokens=item.max_tokens)
        r.t_submit = due
        self.eng.submit(r)
        self.reqs.append(r)
        return r

    def _busy(self) -> bool:
        return bool(self.eng.queue) or any(s is not None
                                           for s in self.eng.slots)

    def _tick(self) -> None:
        t0 = time.monotonic()
        self._sync_s = 0.0
        if self.rec is not None:
            self.rec.tick()
        else:
            self.eng.tick_once()
        self.ticks.append((t0 - self.t_window, time.monotonic() - t0,
                           self._sync_s))

    def open_loop(self) -> None:
        items = traffic_mod.open_loop(self.mix, self.seed, self.vocab,
                                      self.window_s)
        w = self.window_s
        t_base = time.monotonic() + self.mix["preroll_s"]
        self.t_window = t_base
        i = 0
        while True:
            now = time.monotonic()
            if self.rec is not None:
                self.rec.poll(now - t_base)
            while i < len(items) and t_base + items[i].due <= now:
                r = self._submit(items[i], i, t_base + items[i].due)
                if items[i].segment == "window":
                    self.measured.append(r)
                    self.lateness.append(now - r.t_submit)
                i += 1
            if now >= t_base + w and all(r.done for r in self.measured):
                break
            if now >= t_base + w + self.mix["drain_s"] or (
                    i == len(items) and not self._busy()):
                break
            if self._busy():
                self._tick()
            elif i < len(items):
                pause = min(0.05, max(0.0, t_base + items[i].due - now))
                if self.rec is not None:
                    self.rec.wait(pause)
                else:
                    time.sleep(pause)

    def closed_loop(self) -> None:
        stream = traffic_mod.ClosedLoop(self.mix, self.seed, self.vocab)
        t_base = time.monotonic() + self.mix["preroll_s"]
        self.t_window = t_base
        t_end = t_base + self.window_s
        rid = 0
        for _ in range(self.mix["clients"]):
            self._submit(stream.next(), rid, time.monotonic())
            rid += 1
        while True:
            now = time.monotonic()
            if self.rec is not None:
                self.rec.poll(now - t_base)
            if now >= t_end + self.mix["drain_s"] or not self._busy():
                break
            done0 = len(self.eng.completed)
            self._tick()
            now = time.monotonic()
            for _ in self.eng.completed[done0:]:
                if now < t_end:      # the client sends its next request
                    r = self._submit(stream.next(), rid, now)
                    if now >= t_base:
                        self.measured.append(r)
                    rid += 1

    # -- what was measured ----------------------------------------------------

    def metrics(self) -> dict:
        t0, t1 = self.t_window, self.t_window + self.window_s
        if self.mix["kind"] == "open_loop":
            done = [r for r in self.measured if r.done]
            ttft = [r.t_first - r.t_submit for r in done]
            tpot = [(r.t_done - r.t_first) / (len(r.out) - 1)
                    for r in done if len(r.out) > 1]
            return {"ttft_p95_s": {"value": traffic_mod.percentile(ttft, 95),
                                   "unit": "s"},
                    "tpot_p95_s": {"value": traffic_mod.percentile(tpot, 95),
                                   "unit": "s"}}
        toks = sum(len(r.prompt) + len(r.out) for r in self.reqs
                   if r.done and t0 <= r.t_done < t1)
        return {"served_tokens_per_s": {"value": toks / self.window_s,
                                        "unit": "tokens/s"}}

    def side(self) -> dict:
        """Readings that go on an earlier line: the generator's lateness,
        the counts, the queue."""
        done = [r for r in self.measured if r.done]
        lat = np.asarray(self.lateness or [0.0])
        return {"requests_measured": len(self.measured),
                "requests_done": len(done),
                "generator_lateness_median_s": float(np.median(lat)),
                "generator_lateness_max_s": float(lat.max()),
                "preemptions": self.eng.preemptions,
                "queue_at_end": len(self.eng.queue),
                # the longest ticks: [start in the window, wall, of which
                # waiting for the device and sampling], and GC pauses
                "slowest_ticks_s": sorted(self.ticks, key=lambda t: -t[1])[:4],
                "gc_pause_max_s": self.gc_pauses.max_s}

    # -- correctness ------------------------------------------------------------

    def sample(self) -> list[Request]:
        """Finished measured requests, drawn from the seed, the longest
        among them."""
        done = [r for r in self.measured if r.done] or [
            r for r in self.reqs if r.done]
        if not done:
            return []
        n = min(self.mix["check"]["requests"], len(done))
        longest = max(done, key=lambda r: len(r.prompt) + len(r.out))
        rest = [r for r in done if r is not longest]
        rng = traffic_mod.rng_for(self.seed, 2)
        pick = rng.choice(len(rest), size=n - 1, replace=False) if n > 1 \
            else []
        return [longest] + [rest[i] for i in pick]

    def free(self) -> None:
        self.gc_pauses.close()
        self.eng = None
        if self.rec is not None:
            self.rec.eng = None
        gc.collect()

    def check(self, sample: list[Request]) -> list[harness.Check]:
        t0 = time.monotonic()
        w = self.ref.make_weights(self.cfg, self.seed)
        gaps = [self.ref.served_gaps(self.cfg, w, r.prompt, r.out)
                for r in sample]
        harness.log(check_requests=len(sample),
                    check_served_tokens=int(sum(len(g) for g in gaps)),
                    check_s=time.monotonic() - t0)
        limits = self.mix["check"]
        return [harness.Check(k, v, limits[k])
                for k, v in gap_numbers(gaps).items()]


class GcPauses:
    """The longest pause of Python's garbage collector in the run."""

    def __init__(self):
        self.max_s = 0.0
        self._t = None
        gc.callbacks.append(self._on)

    def _on(self, phase, _info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.max_s = max(self.max_s, time.perf_counter() - self._t)

    def close(self) -> None:
        gc.callbacks.remove(self._on)


def gap_numbers(gaps: list[np.ndarray]) -> dict:
    """The numbers compared for ``correct``, from each served token's gap
    below the reference's best: the widest gap, and the share of served
    tokens that are not the reference's best. A lower precision flips
    many near-ties by small gaps, so the share separates it from the
    program where the widest gap alone barely does."""
    if not gaps:
        return {"max_logit_gap": float("inf"), "flipped_share": float("inf")}
    g = np.concatenate(gaps)
    return {"max_logit_gap": float(g.max()),
            "flipped_share": float(np.mean(g > 0))}


def run(cell: harness.Cell, args, clock: harness.Clock,
        counter: harness.CompileCounter, devs) -> bool:
    run = Run(cell, args.seed, args.seconds)
    run.setup()
    setup_s = clock.now()
    if args.trace:
        run.rec = tracing.ServeRecorder(run.eng, cell.traffic["trace"])
    counter.armed = True
    t0 = time.monotonic()
    if cell.traffic["kind"] == "open_loop":
        run.open_loop()
    else:
        run.closed_loop()
    counter.armed = False
    harness.log(loop_s=time.monotonic() - t0, compiles_in_window=counter.count,
                **run.side())
    metrics = run.metrics()
    metrics["setup_s"] = {"value": setup_s, "unit": "s"}
    device = harness.device_record(devs)
    failed = sum(1 for r in run.measured if not r.done)
    attempted = len(run.measured)
    sample = run.sample()
    summary = (tracing.summarize(run.rec.read(), devs)
               if run.rec is not None else None)
    run.free()
    checks = run.check(sample)
    if args.trace:
        metrics, breakdown = tracing.per_layer(cell, summary, device, devs)
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
    else:
        breakdown = None
    return harness.emit(checks=checks, attempted=attempted, failed=failed,
                        metrics=metrics, device=device, breakdown=breakdown)
