"""The traced run: host annotations around the calls into each layer, the
profiler over a part of the measured window, and the context that the
per-layer metric readers read.

The annotations (``jax.profiler.TraceAnnotation``, named ``bench.*``) are
put on from the benchmark's side, around the program's own calls, and
only in a traced run: ``bench.tick`` around ``ServeEngine.tick_once``,
``bench.admit`` around admission, ``bench.prefill`` around one prompt's
prefill (with its token count), ``bench.decode`` around the decode
program's call (with the active slots and their cached lengths),
``bench.sample`` around sampling and the host sync that ends a tick;
``bench.batch`` around a training batch's construction and
``bench.step`` around the train step's call and its sync. Annotations
and device events share the profiler's clock.
"""

from __future__ import annotations

import dataclasses
import shutil
import tempfile

import jax
import numpy as np

import harness
import trace_reduce as tr
import work


def _options():
    o = jax.profiler.ProfileOptions()
    o.python_tracer_level = 0          # no Python call events: too many
    o.host_tracer_level = 2
    return o


class Profiler:
    """The profiler over ``[start_s, start_s + seconds)`` of the window,
    switched by ``poll`` from the driver's loop."""

    def __init__(self, tcfg: dict):
        self.start_s = tcfg["start_s"]
        self.stop_s = tcfg["start_s"] + tcfg["seconds"]
        self.state = "before"
        self.dir = None
        self._win = None

    def poll(self, t_rel: float) -> None:
        if self.state == "before" and t_rel >= self.start_s:
            self.dir = tempfile.mkdtemp(prefix="bench-trace-")
            jax.profiler.start_trace(self.dir, profiler_options=_options())
            self._win = jax.profiler.TraceAnnotation("bench.window")
            self._win.__enter__()
            self.state = "on"
        elif self.state == "on" and t_rel >= self.stop_s:
            self.stop()

    def stop(self) -> None:
        if self.state == "on":
            self._win.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.state = "done"

    def read(self) -> tr.Trace | None:
        self.stop()
        if self.dir is None:
            return None
        try:
            return tr.read(self.dir)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def _annotated(fn, name: str):
    def call(*a, **kw):
        with jax.profiler.TraceAnnotation(name):
            return fn(*a, **kw)
    return call


class ServeRecorder(Profiler):
    """Annotates a ``ServeEngine``'s calls into its layers."""

    def __init__(self, eng, tcfg: dict):
        super().__init__(tcfg)
        self.eng = eng
        eng._admit = _annotated(eng._admit, "bench.admit")
        prefill_slot = eng._prefill_slot

        def prefill(s, req, p0):
            n = len(req.prompt) - 1 - p0
            with jax.profiler.TraceAnnotation("bench.prefill", tokens=n):
                return prefill_slot(s, req, p0)
        eng._prefill_slot = prefill
        decode = eng._decode

        def dec(*a, **kw):
            active = [s for s, r in enumerate(eng.slots) if r is not None]
            keys = int(sum(int(eng._pos[s]) + 1 for s in active))
            with jax.profiler.TraceAnnotation("bench.decode",
                                              active=len(active), keys=keys):
                return decode(*a, **kw)
        eng._decode = dec
        sample = eng.sample

        def smp(logits):
            with jax.profiler.TraceAnnotation("bench.sample"):
                return np.asarray(sample(logits))
        eng.sample = smp

    def tick(self) -> None:
        with jax.profiler.TraceAnnotation("bench.tick"):
            self.eng.tick_once()

    def wait(self, seconds: float) -> None:
        import time
        with jax.profiler.TraceAnnotation("bench.wait_arrival"):
            time.sleep(seconds)


class TrainRecorder(Profiler):
    """Annotates a ``Trainer``'s batch function and step call."""

    def __init__(self, trainer, tcfg: dict):
        super().__init__(tcfg)
        batch_fn = trainer.batch_fn

        def batch(step):
            with jax.profiler.TraceAnnotation("bench.batch", step=step):
                return batch_fn(step)
        trainer.batch_fn = batch
        step_fn = trainer._step_fn

        def step(*a):
            with jax.profiler.TraceAnnotation("bench.step"):
                return jax.block_until_ready(step_fn(*a))
        trainer._step_fn = step


# ---------------------------------------------------------------------------
# what the readers read
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Context:
    """A traced run as the per-layer readers see it: one device's events
    inside the traced window, the host annotations, the cell."""
    trace: tr.Trace
    device: str
    lo: int
    hi: int
    cell: harness.Cell
    peaks: dict

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    @property
    def ops(self) -> list[tr.Ev]:
        return tr.in_window(self.trace.ops.get(self.device, []),
                            self.lo, self.hi)

    @property
    def modules(self) -> list[tr.Ev]:
        return tr.in_window(self.trace.modules.get(self.device, []),
                            self.lo, self.hi)

    def host(self, name: str) -> list[tr.Ev]:
        return [e for e in tr.in_window(self.trace.host, self.lo, self.hi)
                if e.name == name]

    def busy_s(self, lo: int | None = None, hi: int | None = None) -> float:
        return tr.busy_ns(self.trace.ops.get(self.device, []),
                          self.lo if lo is None else lo,
                          self.hi if hi is None else hi) * 1e-9

    def module_calls(self, name: str) -> list[tr.Ev]:
        return [m for m in self.modules if tr.module_name(m) == name]

    def ops_in(self, modules: list[tr.Ev]) -> list[tr.Ev]:
        return tr.inside(self.ops, [(m.start, m.end) for m in modules])

    def last_host_before(self, name: str, t: int) -> tr.Ev | None:
        best = None
        for e in self.trace.host:
            if e.start > t:
                break
            if e.name == name:
                best = e
        return best


@dataclasses.dataclass
class Summary:
    trace: tr.Trace | None
    busy_s: float
    window_s: float


def summarize(trace: tr.Trace | None, devs) -> Summary:
    """Device busy time and the traced window's length, averaged over the
    chips used."""
    if trace is None:
        return Summary(None, 0.0, 0.0)
    lo, hi = trace.window()
    names = [str(d.id) for d in devs]
    busy = [tr.busy_ns(trace.ops.get(n, []), lo, hi) for n in names]
    return Summary(trace, float(np.mean(busy)) * 1e-9, (hi - lo) * 1e-9)


def per_layer(cell: harness.Cell, summary: Summary, device: dict,
              devs) -> tuple[dict, dict]:
    """Every per-layer metric of the cell that its reader finds, and the
    breakdown of device time and idle gaps."""
    trace = summary.trace
    lo, hi = trace.window()
    dev = str(devs[0].id)
    ctx = Context(trace, dev, lo, hi, cell, work.peaks(device["kind"]))
    out = {}
    for m in cell.per_layer:
        value = harness.load_reader(m["name"]).read(ctx)
        if value is not None and np.isfinite(value):
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    harness.log(trace_modules=modules(ctx))
    return out, breakdown(ctx)


def modules(ctx: Context, n: int = 8) -> dict:
    """For each program run in the traced window: its calls, its device
    seconds and its ``n`` costliest ops (seconds, custom call or not),
    so that a reader sees what each program spends its time on."""
    out = {}
    for name in sorted({tr.module_name(m) for m in ctx.modules}):
        calls = ctx.module_calls(name)
        ops = ctx.ops_in(calls)
        top = sorted(tr.by_name(ops).items(), key=lambda kv: -kv[1])[:n]
        custom = {tr.op_name(o) for o in ops if tr.is_custom_call(o)}
        out[name] = {"calls": len(calls),
                     "device_s": sum(c.dur for c in calls) * 1e-9,
                     "top_ops": [[k, v * 1e-9, k in custom] for k, v in top]}
    return out


def breakdown(ctx: Context, n: int = 10) -> dict:
    ops = sorted(tr.by_name(ctx.ops).items(), key=lambda kv: -kv[1])[:n]
    idle: dict[str, int] = {}
    for s, e in tr.gaps(ctx.trace.ops.get(ctx.device, []), ctx.lo, ctx.hi):
        k = tr.host_at(ctx.trace.host, (s + e) // 2)
        idle[k] = idle.get(k, 0) + (e - s)
    gaps = sorted(idle.items(), key=lambda kv: -kv[1])[:n]
    return {"device_ops": [[k, v * 1e-9] for k, v in ops],
            "idle_gaps": [[k, v * 1e-9] for k, v in gaps]}


# ---------------------------------------------------------------------------
# reductions the readers share
# ---------------------------------------------------------------------------


def idle_share(ctx: Context) -> float:
    """Percent of the traced window in which no op ran on the device."""
    return 100.0 * (1.0 - ctx.busy_s() / ctx.window_s)


def decode_calls(ctx: Context, module: str) -> list[tuple[tr.Ev, tr.Ev]]:
    """Each execution of the decode program in the window, paired with
    the ``bench.decode`` annotation that launched it."""
    out = []
    for m in ctx.module_calls(module):
        a = ctx.last_host_before("bench.decode", m.start)
        if a is not None:
            out.append((m, a))
    return out


def decode_lengths(ann: tr.Ev) -> tuple[int, int]:
    return int(ann.stats["active"]), int(ann.stats["keys"])


def decode_work(cfg: dict, ann: tr.Ev) -> work.Work:
    """Model work of the decode call ``ann`` annotated."""
    rows, keys = decode_lengths(ann)
    mm = sum(work.decoder_matmuls(cfg, rows), work.Work())
    return mm + attention_work(cfg, rows, keys)


def attention_work(cfg: dict, rows: int, keys: int) -> work.Work:
    # decode_attention depends on the lengths only through their sum
    per = [keys // max(rows, 1)] * rows
    if rows:
        per[0] += keys - sum(per)
    return work.decode_attention(cfg, per)


def roofline(ctx: Context, least_s: float, kernel_s: float) -> float | None:
    if kernel_s <= 0 or least_s <= 0:
        return None
    return 100.0 * least_s / kernel_s
