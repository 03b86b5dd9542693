"""Unified PIM observability: tracing, metrics, and drift detection.

Three layers, one switch:

  * **tracing** (``repro.obs.trace``) — structured span events
    (compile/trace, per-node kernel launches, pipeline fill/steady/drain
    ticks, serve admit/prefill/decode/evict) exported as
    Chrome-trace/Perfetto JSON, so a training step or serve run opens as
    a timeline;
  * **metrics** (``repro.obs.metrics``) — a process-local registry of
    counters/gauges/histograms absorbing the stack's ad-hoc counters
    (placed blocks, kernel launches, KV occupancy, router queue depths)
    and adding per-request TTFT/TPOT and per-step wall-time histograms;
  * **drift** (``repro.obs.drift``) — joins measured launch spans
    against the schedule's *modeled* stage costs and reports per-node
    modeled-vs-measured ratios.

Two more records ride on the same layer:

  * **the profiler clock** — while a JAX profiler session is recording,
    every span also opens a ``jax.profiler.TraceAnnotation`` of its name
    with its args as metadata (``repro.obs.trace``), so the program's
    own spans land in the profile beside the device's ops;
  * **the compile record** (``repro.obs.compile_record``) — always on: one
    entry per program built (label, key, trace / lower / backend-compile
    / cache-read seconds, cache hit, first call's wall time, recompile)
    and per mapper phase, fed by one ``jax.monitoring`` listener.

Cost discipline: tracing is **opt-in** (:func:`enable`). When neither
the tracer nor a profiler is on, a span costs one check
(:func:`recording`): no span args are built (hot paths pass them
lazily, ``span(..., lazy=...)``), no ``TraceAnnotation`` is made, no
device syncs happen, and no jit retraces are introduced
(instrumentation wraps ``pallas_call`` dispatch sites and program
boundaries, never traced code, and no ``named_scope`` is put into traced
code). The syncs some spans make (``program:call``, the per-node
``execute`` spans) happen only with the tracer enabled, never for the
profiler alone. The metrics registry and the compile record are
always-on but only touched at program boundaries (per step / tick /
request / compile), where a dict update is noise.

Usage::

    from repro import obs

    tr = obs.enable()                 # fresh Tracer installed globally
    prog(*args)                       # spans recorded
    tr.export_chrome("step.trace.json")
    obs.metrics().snapshot()          # counters/gauges/histograms
    obs.drift_report(prog.schedule)   # modeled-vs-measured per node
    obs.disable()

or scoped::

    with obs.scoped() as tr:
        executor.run(*args)
    report = obs.drift_report(schedule, tr)
"""

from __future__ import annotations

import contextlib

from repro.obs.drift import (DriftReport, NodeDrift, PipelineDrift,
                             StageOccupancy, drift_report, measure_drift,
                             pipeline_drift)
from repro.obs.metrics import (DEFAULT_EDGES, Counter, Gauge, Histogram,
                               MetricsRegistry)
from repro.obs.compile_record import (Build, compiles, mapper_phase, program,
                                reset_compiles)
from repro.obs.trace import (NULL_TRACER, NullTracer, SpanEvent, Tracer,
                             profiler_recording, validate_chrome_trace)

_TRACER: Tracer | NullTracer = NULL_TRACER
_METRICS = MetricsRegistry()


def tracer() -> Tracer | NullTracer:
    """The installed tracer (the shared no-op when disabled)."""
    return _TRACER


def metrics() -> MetricsRegistry:
    """The process-local metrics registry (always available)."""
    return _METRICS


def is_enabled() -> bool:
    return _TRACER.enabled


def recording() -> bool:
    """Whether a span made now would be recorded anywhere: the tracer is
    enabled or a profiler session is recording."""
    return _TRACER.enabled or profiler_recording()


def enable(tracer: Tracer | None = None) -> Tracer:
    """Install (and return) a tracer globally — a fresh one by default."""
    global _TRACER
    _TRACER = tracer if tracer is not None else Tracer()
    return _TRACER


def disable() -> None:
    """Swap the no-op tracer back in (recorded events are dropped with
    the old tracer unless the caller kept a reference)."""
    global _TRACER
    _TRACER = NULL_TRACER


@contextlib.contextmanager
def scoped(tracer: Tracer | None = None):
    """Enable a (fresh) tracer for the block, restoring the previous
    tracer — enabled or not — on exit. Yields the scoped tracer."""
    global _TRACER
    prev = _TRACER
    _TRACER = tracer if tracer is not None else Tracer()
    try:
        yield _TRACER
    finally:
        _TRACER = prev


_NULL_CM = NullTracer._NULL_CM


def span(name: str, lane: str = "main", lazy=None, **args):
    """A span on the installed tracer, and on the profiler clock while a
    profiler is recording; the shared no-op context when neither is on.
    ``lazy``, a function returning a dict, gives args that are built
    only when the span is recorded."""
    tr = _TRACER
    if not (tr.enabled or profiler_recording()):
        return _NULL_CM
    if lazy is not None:
        args.update(lazy())
    return tr.span(name, lane=lane, **args)


def instant(name: str, lane: str = "main", **args) -> None:
    _TRACER.instant(name, lane=lane, **args)


__all__ = [
    "Build", "Counter", "DEFAULT_EDGES", "DriftReport", "Gauge",
    "Histogram", "MetricsRegistry", "NULL_TRACER", "NodeDrift",
    "NullTracer", "PipelineDrift", "SpanEvent", "StageOccupancy", "Tracer",
    "compiles", "disable", "drift_report", "enable", "instant",
    "is_enabled", "mapper_phase", "measure_drift", "metrics",
    "pipeline_drift", "profiler_recording", "program", "recording",
    "reset_compiles", "scoped", "span", "tracer", "validate_chrome_trace",
]
