"""The benchmark rehearsed on the CPU at smoke sizes: each cell's traffic,
loop, checks and metric arithmetic through the same functions as on the
chip, with the Pallas kernels interpreted. The smoke cells live in a
copy of the benchmark, added as new files and new entries only, with one
throw-away mix that exists only in this test."""

import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH / "tests"))

import smoke_tree  # noqa: E402

# a mix that no file of the benchmark names: added from here, it runs
# with no edit to any existing file
THROWAWAY = {"smoke-burst": dict(smoke_tree.CHAT, rate_per_s=12.0,
                                 slots=3)}


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return smoke_tree.build(tmp_path_factory.mktemp("checkout"),
                            extra_traffic=THROWAWAY)


def _env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return env


def run_cell(tree, workload, trace, seed=2 ** 31 + 7, seconds=2.0,
             prelude=""):
    code = (f"import sys\n"
            f"sys.path.insert(0, {str(tree / 'bench' / 'tests')!r})\n"
            f"sys.path.insert(0, {str(tree / 'src')!r})\n{prelude}\n"
            "import cpu_run\n"
            f"cpu_run.main(['--workload', {workload!r}, '--seed', "
            f"'{seed}', '--seconds', '{seconds}', '--trace', '{trace}'])")
    p = subprocess.run([sys.executable, "-c", code], cwd=tree, env=_env(),
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1]), p.stderr


def _spec(tree):
    return json.loads((tree / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("workload,trace", [
    ("smoke.smoke-chat", 0), ("smoke.smoke-chat", 1),
    ("smoke.smoke-long", 0), ("smoke.smoke-train", 0),
    ("smoke.smoke-train", 1), ("smoke.smoke-burst", 0)])
def test_cell_rehearsal(tree, workload, trace):
    line, err = run_cell(tree, workload, trace)
    assert list(line)[-1] == "checks"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    spec = _spec(tree)
    mine = [m for m in (spec["per_layer"] if trace else spec["end_to_end"])
            if workload in m.get("workloads", [workload])]
    names = {m["name"] for m in mine}
    assert set(line["metrics"]) <= names
    if not trace:
        assert set(line["metrics"]) == names
        assert all(v["value"] > 0 for v in line["metrics"].values())
    else:
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
        assert "busy_s" in line["device"] and line["device"]["window_s"] > 0
    units = {m["name"]: m["unit"] for m in mine}
    assert all(v["unit"] == units[k] for k, v in line["metrics"].items())
    # the numbers compared are the last lines of standard error
    last = err.strip().splitlines()[-len(line["checks"]):]
    assert all(s.startswith("check ") and " limit " in s for s in last)


def test_run_refuses_a_cpu(tree):
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "qwen2.5-32b-4l.chat", "--seed", "1", "--seconds",
                        "1", "--trace", "0"], cwd=tree, env=_env(),
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and '"correct"' not in p.stdout


def test_run_fails_with_the_benchmark_alone(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(BENCH.parent / "BENCHMARK.json", tmp_path)
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "qwen2.5-32b-4l.chat", "--seed", "1", "--seconds",
                        "1", "--trace", "0"], cwd=tmp_path, env=_env(),
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and '"correct"' not in p.stdout
