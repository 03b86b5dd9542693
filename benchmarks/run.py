"""Benchmark harness — one module per paper table/figure (+ beyond-paper).

Prints ``name,value,derived`` CSV rows. Usage:
    PYTHONPATH=src python -m benchmarks.run [module ...]
    PYTHONPATH=src python -m benchmarks.run --only mod[,mod...]

Exits non-zero if any registered benchmark raises, so CI can run the
whole suite as a smoke test. Every ``BENCH_*.json`` artifact a run
(re)writes is stamped with provenance — the git SHA and UTC timestamp it
was produced at — so a committed perf-trajectory number can always be
traced back to the tree that produced it
(``scripts/validate_bench.py`` enforces the stamp).
"""

import datetime
import importlib
import json
import pathlib
import subprocess
import sys
import traceback

ROOT = pathlib.Path(__file__).resolve().parent.parent

# imported lazily per run so one module's import-time failure cannot take
# down the rest of the suite
MODULES = (
    "table1_cell",
    "fig5_mac",
    "fig6_training",
    "fa_steps",
    "fp_procedure",
    "ultrafast_ablation",
    "arch_pim_cost",
    "roofline",
    "kernel_bench",
    "mapper_bench",
    "executor_bench",
    "fusion_bench",
    "pipeline_bench",
    "serve_bench",
    "quant_bench",
    "traffic_bench",
    "kvquant_bench",
)


def _git_sha() -> str:
    try:
        out = subprocess.run(["git", "rev-parse", "HEAD"], cwd=ROOT,
                             capture_output=True, text=True, timeout=10)
        sha = out.stdout.strip()
        return sha if out.returncode == 0 and sha else "unknown"
    except (OSError, subprocess.SubprocessError):
        return "unknown"


def stamp_provenance(paths=None) -> list[str]:
    """Write ``provenance: {git_sha, utc}`` into each BENCH artifact
    (default: every ``BENCH_*.json`` in the repo root). Idempotent —
    restamping just refreshes the stamp. Returns the stamped names."""
    paths = (sorted(ROOT.glob("BENCH_*.json")) if paths is None
             else [pathlib.Path(p) for p in paths])
    prov = {"git_sha": _git_sha(),
            "utc": datetime.datetime.now(datetime.timezone.utc).isoformat()}
    stamped = []
    for p in paths:
        try:
            data = json.loads(p.read_text())
        except (OSError, json.JSONDecodeError):
            continue
        if not isinstance(data, dict):
            continue
        data["provenance"] = prov
        p.write_text(json.dumps(data, indent=1, sort_keys=True) + "\n")
        stamped.append(p.name)
    return stamped


def _parse_args(argv: list[str]) -> list[str]:
    """Positional module names, plus ``--only mod[,mod...]`` (or
    ``--only=...``) as an explicit filter form — both select from
    ``MODULES``; no arguments runs the whole suite."""
    names = []
    it = iter(argv)
    for a in it:
        if a == "--only":
            a = next(it, None)
            if a is None:
                print("--only needs a module list", file=sys.stderr)
                raise SystemExit(2)
            names.extend(m for m in a.split(",") if m)
        elif a.startswith("--only="):
            names.extend(m for m in a[len("--only="):].split(",") if m)
        elif a.startswith("-"):
            print(f"unknown flag: {a}", file=sys.stderr)
            raise SystemExit(2)
        else:
            names.append(a)
    return names or list(MODULES)


def main() -> None:
    names = _parse_args(sys.argv[1:])
    unknown = [n for n in names if n not in MODULES]
    if unknown:
        print(f"unknown benchmark(s): {unknown}; have {list(MODULES)}",
              file=sys.stderr)
        raise SystemExit(2)
    from repro.launch.cache import use_compile_cache
    use_compile_cache(ROOT)
    print("name,value,derived")
    failed = []
    for name in names:
        try:
            mod = importlib.import_module(f"benchmarks.{name}")
            for row in mod.run():
                print(row)
        except Exception:
            traceback.print_exc()
            print(f"BENCHMARK FAILED: {name}", file=sys.stderr)
            failed.append(name)
    stamped = stamp_provenance()
    if stamped:
        print(f"stamped provenance into {stamped}", file=sys.stderr)
    if failed:
        print(f"failed benchmarks: {failed}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
