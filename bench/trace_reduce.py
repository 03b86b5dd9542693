"""From a profiler trace to device busy time, kernel times and idle gaps.

``read`` loads the ``.xplane.pb`` that ``jax.profiler`` writes, through
``jax.profiler.ProfileData``, into plain event lists: each device's
synchronous XLA ops and its XLA modules (program executions), and the
host annotations the benchmark opened (``bench.*``). Everything after
that is interval arithmetic on those lists, so it can be checked on a
hand-made list as well as on a recorded trace.

On a TPU the device planes are ``/device:TPU:<n>``, with lines
``XLA Ops`` and ``XLA Modules``; op events are named by their HLO text
(``%pim_matmul_grouped.11 = f32[...] custom-call(...)``). A trace
recorded on the CPU has no device plane: its ops run on host threads and
carry ``hlo_op`` and ``hlo_module`` stats, and ``read`` files them under
the device ``cpu`` so that the reduction can be tested here. The
benchmark itself refuses to run anywhere but on a TPU.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

HOST_PREFIX = "bench."
_OP_NAME = re.compile(r"^%?([A-Za-z_][\w\-]*?)(?:\.\d+)?(?:\s|=|$)")
_MODULE_NAME = re.compile(r"^(.*?)(?:\(\d+\))?$")


@dataclasses.dataclass
class Ev:
    name: str
    start: int          # ns
    end: int            # ns
    stats: dict = dataclasses.field(default_factory=dict)

    @property
    def dur(self) -> int:
        return self.end - self.start


@dataclasses.dataclass
class Trace:
    ops: dict           # device -> [Ev], synchronous device ops
    modules: dict       # device -> [Ev], program executions
    host: list          # [Ev], the benchmark's host annotations

    def window(self) -> tuple[int, int]:
        """The span of the ``bench.window`` annotation, else of every
        event."""
        w = [e for e in self.host if e.name == HOST_PREFIX + "window"]
        if w:
            return w[0].start, w[0].end
        evs = [e for v in self.ops.values() for e in v] + self.host
        return min(e.start for e in evs), max(e.end for e in evs)


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def read(path: str) -> Trace:
    """Load a trace file (or the newest one under a log directory)."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        path = find_xplane(path)
    pd = ProfileData.from_file(path)
    ops: dict = {}
    modules: dict = {}
    host: list = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = plane.name.rsplit(":", 1)[1]
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.setdefault(dev, []).extend(_evs(line))
                elif line.name == "XLA Modules":
                    modules.setdefault(dev, []).extend(_evs(line))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in _evs(line):
                    if e.name.startswith(HOST_PREFIX):
                        host.append(e)
                    elif "hlo_op" in e.stats and not e.name.startswith(
                            "end:"):
                        ops.setdefault("cpu", []).append(e)
                        mod = str(e.stats.get("hlo_module", ""))
                        modules.setdefault("cpu", []).append(
                            Ev(mod, e.start, e.end))
    for d in ops.values():
        d.sort(key=lambda e: e.start)
    for d in modules.values():
        d.sort(key=lambda e: e.start)
    host.sort(key=lambda e: e.start)
    return Trace(ops=ops, modules=modules, host=host)


def _evs(line) -> list[Ev]:
    out = []
    for e in line.events:
        s = int(e.start_ns)
        out.append(Ev(e.name, s, s + int(e.duration_ns),
                      {k: v for k, v in e.stats}))
    return out


# ---------------------------------------------------------------------------
# names
# ---------------------------------------------------------------------------


def op_name(ev: Ev) -> str:
    """An op's short name: ``%pim_matmul_grouped.11 = ...`` gives
    ``pim_matmul_grouped``; ``fusion.44`` gives ``fusion``."""
    m = _OP_NAME.match(ev.name)
    return m.group(1) if m else ev.name


def is_custom_call(ev: Ev) -> bool:
    return "custom-call(" in ev.name or "tpu_custom_call" in ev.name


def module_name(ev: Ev) -> str:
    """A program's name without its fingerprint: ``jit_fn(8704...)``
    gives ``jit_fn``."""
    return _MODULE_NAME.match(ev.name).group(1)


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------


def clip(spans, lo: int, hi: int) -> list[tuple[int, int]]:
    out = []
    for s, e in spans:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            out.append((s, e))
    return out


def union(spans) -> list[tuple[int, int]]:
    """Merge overlapping intervals."""
    out: list[list[int]] = []
    for s, e in sorted(spans):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(evs, lo: int, hi: int) -> int:
    """Time in ``[lo, hi)`` during which at least one event runs."""
    return sum(e - s for s, e in union(clip(((x.start, x.end) for x in evs),
                                            lo, hi)))


def gaps(evs, lo: int, hi: int) -> list[tuple[int, int]]:
    """The idle intervals of ``[lo, hi)``: where no event runs."""
    out, t = [], lo
    for s, e in union(clip(((x.start, x.end) for x in evs), lo, hi)):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def host_at(host: list[Ev], t: int, skip=("bench.window",)) -> str:
    """The innermost benchmark annotation open at ``t``."""
    best = None
    for e in host:
        if e.start > t:
            break
        if e.end > t and e.name not in skip:
            if best is None or e.start >= best.start:
                best = e
    return best.name if best is not None else "none"


def inside(evs, spans) -> list[Ev]:
    """Events that start inside one of ``spans`` (sorted, disjoint)."""
    out, spans = [], sorted(spans)
    j = 0
    for e in evs:
        while j < len(spans) and spans[j][1] <= e.start:
            j += 1
        if j < len(spans) and spans[j][0] <= e.start < spans[j][1]:
            out.append(e)
    return out


def by_name(evs, key=op_name) -> dict[str, int]:
    """Summed duration (ns) per name."""
    out: dict[str, int] = {}
    for e in evs:
        k = key(e)
        out[k] = out.get(k, 0) + e.dur
    return out


def in_window(evs, lo: int, hi: int) -> list[Ev]:
    return [e for e in evs if lo <= e.start and e.end <= hi]
