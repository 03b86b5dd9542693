"""What every cell shares: the spec, the files found by name, the device,
the compile counter and the result line.

A cell is one entry of ``workloads`` in ``BENCHMARK.json``. Its
configuration is ``configs/<config>.json``, its traffic mix
``traffic/<traffic>.json``, its program adapter ``adapters/<family>.py``
(named by the configuration), its driver ``drivers/<driver>.py`` (named by
the configuration too) and each of its per-layer metrics
``metrics/<metric>.py``. Adding a cell, a mix or a metric adds files and
entries; nothing here changes.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import os
import pathlib
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
SPEC_FILE = ROOT / "BENCHMARK.json"


class NoDevice(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict
    traffic: dict
    end_to_end: list[dict]
    per_layer: list[dict]


def load_spec() -> dict:
    return json.loads(SPEC_FILE.read_text())


def _for_cell(metrics: list[dict], cell: str) -> list[dict]:
    return [m for m in metrics if cell in m.get("workloads", [cell])]


def load_cell(name: str, spec: dict | None = None) -> Cell:
    spec = spec or load_spec()
    by_name = {w["name"]: w for w in spec["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r}; known: {sorted(by_name)}")
    w = by_name[name]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    config = json.loads((ROOT / conf["file"]).read_text())
    traffic = json.loads((BENCH / "traffic" / f"{w['traffic']}.json")
                         .read_text())
    return Cell(name=name, chips=w["chips"], config_name=w["config"],
                traffic_name=w["traffic"], config=config, traffic=traffic,
                end_to_end=_for_cell(spec["end_to_end"], name),
                per_layer=_for_cell(spec["per_layer"], name))


def load_module(kind: str, name: str):
    """``<kind>/<name>.py`` under the benchmark, imported by path (names
    may hold dots)."""
    path = BENCH / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def prefill_buckets(traffic: dict, window_s: float) -> dict[int, int]:
    """The padded prefill lengths a run of the mix reaches, each with one
    prompt length that reaches it: the engine prefills all but a prompt's
    last token, padded up to a block-size multiple."""
    import traffic as traffic_mod
    bs = traffic["kv_block_size"]
    return {-(-(n - 1) // bs) * bs: n
            for n in sorted(traffic_mod.prompt_lengths(traffic, window_s))}


def load_reader(metric: str):
    """The reader of a per-layer metric: ``metrics/<metric>.py``, or, for
    a metric named ``<quantity>.<cells>`` that has no file of its own,
    the quantity's shared ``metrics/<quantity>.py``."""
    if (BENCH / "metrics" / f"{metric}.py").exists():
        return load_module("metrics", metric)
    return load_module("metrics", metric.split(".", 1)[0])


# ---------------------------------------------------------------------------
# process set-up: the compile cache, the device, the compile counter
# ---------------------------------------------------------------------------


def use_compile_cache() -> str:
    """JAX's persistent cache, kept as the program keeps it
    (``JAX_COMPILATION_CACHE_DIR``, else the fixed ``.jax_cache`` in the
    checkout), so that only the first run of a cell in a checkout
    compiles. Also keeps programs of any size, and keeps libtpu from
    logging to its default fixed directory under ``/tmp``."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from repro.launch.cache import use_compile_cache as program_cache
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return program_cache(ROOT)


def require_chips(n: int) -> list:
    """The first ``n`` TPU devices, or ``NoDevice``."""
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:           # no backend at all
        raise NoDevice(str(e)) from e
    if devs[0].platform != "tpu":
        raise NoDevice(f"needs a TPU, JAX found {devs[0].platform}")
    if len(devs) < n:
        raise NoDevice(f"needs {n} chips, JAX found {len(devs)}")
    return devs[:n]


def device_record(devs) -> dict:
    peak = 0
    for d in devs:
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


class CompileCounter:
    """Counts programs lowered (a trace that compiles or loads from the
    persistent cache) while ``armed``: inside the measured window this
    should stay 0."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        from jax import monitoring
        self.armed = False
        self.count = 0
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, _dur, **_kw):
        if self.armed and name == self.EVENT:
            self.count += 1


# ---------------------------------------------------------------------------
# the result
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Check:
    """One number compared for ``correct``, beside its limit; passes
    when ``value <= limit``."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


def log(**fields) -> None:
    """An earlier line of standard output: progress and side readings."""
    print(json.dumps(fields, default=float), flush=True)


def emit(*, checks: list[Check], attempted: int, failed: int,
         metrics: dict, device: dict, breakdown: dict | None = None) -> bool:
    """Print the checks as the last lines of standard error and the
    result as the last line of standard output. Returns ``correct``."""
    correct = bool(checks) and all(c.ok for c in checks) and failed == 0
    for c in checks:
        print(f"check {c.name} = {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.ok else 'FAIL'}", file=sys.stderr, flush=True)
    # a metric with nothing to read is left out, never printed as NaN
    metrics = {k: v for k, v in metrics.items()
               if v["value"] is not None and math.isfinite(v["value"])}
    line = {"correct": correct, "attempted": attempted, "failed": failed,
            "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = {c.name: {"value": c.value if math.isfinite(c.value)
                               else None, "limit": c.limit}
                      for c in checks}
    print(json.dumps(line), flush=True)
    return correct


class Clock:
    """Seconds since the process started (set-up is measured from
    there)."""

    def __init__(self):
        self.t0 = time.monotonic() - _since_start()

    def now(self) -> float:
        return time.monotonic() - self.t0


def _since_start() -> float:
    """Seconds the process has run before this call, from the kernel's
    process start time where it is known."""
    try:
        ticks = os.sysconf("SC_CLK_TCK")
        start = int(pathlib.Path("/proc/self/stat").read_text()
                    .rsplit(")", 1)[1].split()[19]) / ticks
        uptime = float(pathlib.Path("/proc/uptime").read_text().split()[0])
        return max(0.0, uptime - start)
    except (OSError, ValueError, IndexError):
        return 0.0
