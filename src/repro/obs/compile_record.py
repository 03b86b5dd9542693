"""The compile record: where set-up time goes, one entry per program built.

Every program the system builds gets one :class:`Build` in the record
(:func:`compiles`): its label and key (``serve.decode``,
``serve.prefill[<bucket>]``, ``train.step``, ``mapper.<phase>``), the
seconds JAX spent tracing it to a jaxpr, lowering it to MLIR, compiling
it in the backend and reading it back from the persistent compilation
cache, whether that cache hit, and the wall time of the call that built
it. The record is always on and costs nothing on a warm call:

* the engine, the trainer and the mapper open a label around their own
  jitted calls (:func:`program`) — an object, two assignments and a
  clock read, no sync;
* one listener on ``jax.monitoring``, registered once at import, takes
  JAX's own compile events and files each under the label open at that
  moment. A call that hits jit's in-memory cache emits no event, so it
  records nothing; an event with no label open (a reference, user code)
  is not recorded;
* a label and key built once and built again count as a recompile: the
  second entry carries ``recompile=True`` and its key, which answers
  "which step recompiled".

The mapper's Python phases (:func:`mapper_phase`: ``build_schedule``,
``place_kv``, ``compile_schedule``) record their own wall seconds in the
same record, less whatever JAX compile time ran inside them, so that the
trace, compile and mapper readings partition set-up without overlap.

Seconds are exclusive: an event nested inside another of the same build
(a jit traced inside the trace of the program that calls it) is counted
once, under its own kind, and the enclosing event keeps only the rest.
``backend_s`` is JAX's backend-compile event, which encloses the
persistent-cache read: ``compile_s`` is what is left of it after
``retrieval_s``.

Single-threaded by design, like the tracer: the label lives in one
module-level slot, and JAX emits its compile events on the thread that
compiles.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any

from jax import monitoring

# JAX's compile events (jax._src.dispatch / compiler / compilation_cache)
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
HIT_EVENT = "/jax/compilation_cache/cache_hits"
MISS_EVENT = "/jax/compilation_cache/cache_misses"

_SPAN_FIELDS = {TRACE_EVENT: "trace_s", LOWER_EVENT: "lower_s",
                BACKEND_EVENT: "backend_s"}


@dataclasses.dataclass
class Build:
    """One program built (or one mapper phase run) and what it cost."""

    label: str
    key: Any = None
    trace_s: float = 0.0          # jaxpr tracing
    lower_s: float = 0.0          # jaxpr -> MLIR module
    backend_s: float = 0.0        # backend compile, cache read included
    retrieval_s: float = 0.0      # persistent-cache read (on a hit)
    cache_hit: bool | None = None  # None: the persistent cache was not used
    wall_s: float = 0.0           # the building call / the phase, wall
    mapper_s: float = 0.0         # a mapper phase's own Python seconds
    recompile: bool = False

    @property
    def name(self) -> str:
        return self.label if self.key is None else f"{self.label}[{self.key}]"

    @property
    def compile_s(self) -> float:
        """Backend compile seconds, the persistent-cache read excluded."""
        return max(0.0, self.backend_s - self.retrieval_s)

    @property
    def jax_s(self) -> float:
        """Every JAX compile second of this build: trace, lower, backend
        (cache read included)."""
        return self.trace_s + self.lower_s + self.backend_s


_RECORD: list[Build] = []
_BUILT: set[tuple[str, Any]] = set()
_OPEN: list = [None]              # the innermost open label, or None


class _Label:
    """The label open around one call; creates its :class:`Build` on
    the first compile event inside it."""

    __slots__ = ("label", "key", "build", "t0", "prev", "spans")

    def __init__(self, label: str, key: Any = None):
        self.label = label
        self.key = key
        self.build = None
        self.spans = []          # top-level (start, end) event intervals

    def __enter__(self):
        self.prev = _OPEN[0]
        _OPEN[0] = self
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        _OPEN[0] = self.prev
        if self.build is not None:
            self.build.wall_s = time.perf_counter() - self.t0
        return False

    def entry(self) -> Build:
        if self.build is None:
            ident = (self.label, self.key)
            self.build = Build(self.label, self.key,
                               recompile=ident in _BUILT)
            _BUILT.add(ident)
            _RECORD.append(self.build)
        return self.build

    def add_span(self, field: str, start: float, end: float) -> None:
        """Charge one JAX compile event to ``field``. Events arrive as
        they end, so an earlier top-level interval that starts inside
        this one is nested in it: it keeps its own seconds and this
        event is charged only the rest."""
        b = self.entry()
        inner = 0.0
        while self.spans and self.spans[-1][0] >= start:
            s, e = self.spans.pop()
            inner += e - s
        self.spans.append((start, end))
        setattr(b, field, getattr(b, field) + max(0.0, end - start - inner))


class _Phase(_Label):
    """A mapper phase: always one entry, wall seconds less JAX's."""

    __slots__ = ()

    def __enter__(self):
        super().__enter__()
        self.build = Build(self.label)
        _RECORD.append(self.build)
        return self

    def __exit__(self, *exc):
        super().__exit__(*exc)
        b = self.build
        b.mapper_s = max(0.0, b.wall_s - b.jax_s)
        return False


def program(label: str, key: Any = None) -> _Label:
    """The label around one call of a jitted program: a compile inside it
    is recorded under ``label`` (and ``key``, e.g. a prefill bucket)."""
    return _Label(label, key)


def mapper_phase(name: str) -> _Phase:
    """Record one mapper phase ``mapper.<name>``: its wall seconds, less
    the JAX compile time inside it (filed under the same entry)."""
    return _Phase(f"mapper.{name}")


def compiles() -> list[Build]:
    """The record: every program built and every mapper phase run in
    this process, in order (see :func:`reset_compiles`)."""
    return list(_RECORD)


def reset_compiles() -> None:
    """Forget the record and which labels were built."""
    _RECORD.clear()
    _BUILT.clear()


class _Listener:
    """JAX's compile events, filed under the open label. One object,
    registered once on each ``jax.monitoring`` stream it reads."""

    def span(self, event: str, start: float, end: float, **_kw) -> None:
        lab = _OPEN[0]
        if lab is None:
            return
        field = _SPAN_FIELDS.get(event)
        if field is not None:
            lab.add_span(field, start, end)

    def duration(self, event: str, secs: float, **_kw) -> None:
        lab = _OPEN[0]
        if lab is not None and event == RETRIEVAL_EVENT:
            lab.entry().retrieval_s += secs

    def event(self, event: str, **_kw) -> None:
        lab = _OPEN[0]
        if lab is None:
            return
        if event == HIT_EVENT:
            lab.entry().cache_hit = True
        elif event == MISS_EVENT:
            lab.entry().cache_hit = False


_LISTENER = _Listener()
monitoring.register_event_time_span_listener(_LISTENER.span)
monitoring.register_event_duration_secs_listener(_LISTENER.duration)
monitoring.register_event_listener(_LISTENER.event)
