"""Validate the committed BENCH_*.json perf-trajectory artifacts.

Every ``BENCH_*.json`` in the repo root must parse as JSON, carry a
``provenance`` stamp (the git SHA + UTC timestamp ``benchmarks/run.py``
writes, so a committed number is traceable to the tree that produced
it), and the files CI gates on must carry their gate fields with sane
values — a benchmark refactor that silently drops a gated field would
otherwise turn the CI gate into a no-op. Run from the repo root (CI
does)::

    python scripts/validate_bench.py

Exits non-zero with a per-file report on any violation.
"""

from __future__ import annotations

import datetime
import json
import math
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent

# file stem -> {variant: [required numeric gate fields]}
GATES = {
    "BENCH_executor": {
        "lenet5_forward": ["speedup", "trace_count"],
        "llama3_8b_decode": ["speedup", "trace_count"],
    },
    "BENCH_fusion": {
        "llama3_8b_decode": ["matmul_launch_reduction"],
    },
    "BENCH_pipeline": {
        "lenet5_train_modeled": ["speedup"],
        "llama3_8b_smoke_expanded_modeled": ["speedup",
                                             "steady_tokens_per_s",
                                             "interval_s"],
        "llama3_8b_async_measured": ["speedup", "t_sequential_s",
                                     "t_async_s", "dispatch_fraction",
                                     "parity_max_dev", "cpu_count"],
    },
    "BENCH_serve": {
        "paged_router_2": ["speedup_vs_contiguous_1", "ttft_p50_s",
                           "ttft_p95_s", "tpot_p50_s", "tpot_p95_s"],
    },
    "BENCH_quant": {
        "llama3_8b_smoke": ["replica_ratio_int8", "latency_ratio_int8",
                            "max_layer_error_int8", "tokens_per_s_int8"],
    },
    "BENCH_traffic": {
        "static": ["goodput_per_tick", "ttft_p95_ticks"],
        "continuous": ["goodput_per_tick", "ttft_p95_ticks",
                       "goodput_ratio", "ttft_p95_ratio", "preemptions"],
        "oom_demo": ["baseline_ooms", "continuous_ooms", "completed"],
    },
    "BENCH_kvquant": {
        "capacity": ["pool_bytes", "block_ratio", "blocks_fp32"],
        "fp32": ["goodput_per_tick", "preemptions"],
        "fp8": ["goodput_per_tick", "goodput_ratio", "preemptions"],
        "oom_demo": ["fp32_ooms", "fp8_ooms", "fp8_completed"],
    },
}

# gated variants that may instead record why they were not measured
# (the in-process async pipeline needs 4 devices)
MAY_BE_UNMEASURED = {("BENCH_pipeline", "llama3_8b_async_measured")}


def _check_provenance(path: pathlib.Path, data: dict,
                      errors: list[str]) -> None:
    prov = data.get("provenance")
    if not isinstance(prov, dict):
        errors.append(f"{path.name}: missing provenance stamp (rerun "
                      f"benchmarks/run.py to stamp git_sha + utc)")
        return
    sha = prov.get("git_sha")
    if not isinstance(sha, str) or not sha:
        errors.append(f"{path.name}: provenance.git_sha must be a "
                      f"non-empty string, got {sha!r}")
    utc = prov.get("utc")
    try:
        datetime.datetime.fromisoformat(utc)
    except (TypeError, ValueError):
        errors.append(f"{path.name}: provenance.utc must be an ISO-8601 "
                      f"timestamp, got {utc!r}")


def _check(path: pathlib.Path, errors: list[str]) -> None:
    try:
        data = json.loads(path.read_text())
    except (OSError, json.JSONDecodeError) as e:
        errors.append(f"{path.name}: does not parse: {e}")
        return
    if not isinstance(data, dict) or not data:
        errors.append(f"{path.name}: expected a non-empty JSON object")
        return
    _check_provenance(path, data, errors)
    for variant, fields in GATES.get(path.stem, {}).items():
        block = data.get(variant)
        if not isinstance(block, dict):
            errors.append(f"{path.name}: missing gated variant "
                          f"{variant!r}")
            continue
        if (path.stem, variant) in MAY_BE_UNMEASURED \
                and isinstance(block.get("not_measured"), str):
            continue
        for f in fields:
            v = block.get(f)
            if not isinstance(v, (int, float)) or isinstance(v, bool) \
                    or not math.isfinite(v):
                errors.append(f"{path.name}: {variant}.{f} must be a "
                              f"finite number, got {v!r}")


def main() -> int:
    bench_files = sorted(ROOT.glob("BENCH_*.json"))
    errors: list[str] = []
    if not bench_files:
        errors.append("no BENCH_*.json files found in repo root")
    missing = [stem for stem in GATES
               if not (ROOT / f"{stem}.json").exists()]
    for stem in missing:
        errors.append(f"{stem}.json: gated file missing from repo root")
    for path in bench_files:
        _check(path, errors)
    if errors:
        print("bench artifact validation FAILED:", file=sys.stderr)
        for e in errors:
            print(f"  - {e}", file=sys.stderr)
        return 1
    gated = sum(len(v) for g in GATES.values() for v in g.values())
    print(f"ok: {len(bench_files)} BENCH_*.json parse; "
          f"{gated} gate fields present")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
