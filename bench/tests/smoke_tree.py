"""Build a checkout-like tree with smoke-size cells added, for the CPU
rehearsal: a copy of ``BENCHMARK.json`` and ``bench/``, with new
configuration and traffic files and new entries, and no file edited."""

from __future__ import annotations

import json
import pathlib
import shutil

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent

SMOKE_QWEN = dict(hidden_size=64, intermediate_size=128,
                  num_attention_heads=4, num_key_value_heads=2,
                  vocab_size=256)

CHAT = {"kind": "open_loop", "slots": 4, "kv_block_size": 4,
        "kv_blocks": 64, "rate_per_s": 6.0, "preroll_s": 0.5,
        "drain_s": 30,
        "prompt": {"dist": "lognormal", "median": 12, "sigma": 0.5,
                   "min": 5, "max": 21},
        "output": {"dist": "lognormal", "median": 4, "sigma": 0.5,
                   "min": 2, "max": 6},
        "check": {"requests": 3, "max_logit_gap": 0.01, "flipped_share": 0.2},
        "trace": {"start_s": 0.2, "seconds": 1.0}}

LONG = {"kind": "closed_loop", "slots": 2, "clients": 3,
        "kv_block_size": 4, "kv_blocks": 48, "preroll_s": 0.5,
        "drain_s": 30,
        "prompt": {"dist": "lognormal", "median": 24, "sigma": 0.3,
                   "min": 17, "max": 33},
        "output": {"dist": "uniform", "min": 1, "max": 3},
        "check": {"requests": 3, "max_logit_gap": 0.01, "flipped_share": 0.2},
        "trace": {"start_s": 0.2, "seconds": 1.0}}

TRAIN = {"kind": "train", "batch": 8, "dataset_images": 40,
         "check": {"loss_rel_gap": 1e-5, "grad_norm_rel_gap": 7e-7,
                   "update_norm_rel_gap": 1e-4},
         "trace": {"start_s": 0.1, "seconds": 1.0}}


def build(dst: pathlib.Path, extra_traffic: dict | None = None) -> pathlib.Path:
    """Copy the benchmark into ``dst`` and add smoke cells:
    ``smoke.chat``, ``smoke.long`` and ``smoke.train`` (plus one cell per
    entry of ``extra_traffic`` on the smoke qwen configuration)."""
    shutil.copytree(BENCH, dst / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (dst / "src").symlink_to(ROOT / "src")
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    q = json.loads((BENCH / "configs" / "qwen2.5-32b-4l.json")
                   .read_text())
    q.update(SMOKE_QWEN)
    _write(dst / "bench" / "configs" / "smoke-qwen2.json", q)
    spec["configs"].append({"name": "smoke-qwen2", "source": "smoke",
                            "file": "bench/configs/smoke-qwen2.json",
                            "reduced": [], "why": "smoke"})
    spec["configs"].append({"name": "smoke-lenet5", "source": "smoke",
                            "file": "bench/configs/lenet5.json",
                            "reduced": [], "why": "smoke"})
    mixes = {"smoke-chat": CHAT, "smoke-long": LONG, "smoke-train": TRAIN,
             **(extra_traffic or {})}
    for name, mix in mixes.items():
        _write(dst / "bench" / "traffic" / f"{name}.json", mix)
        conf = "smoke-lenet5" if mix["kind"] == "train" else "smoke-qwen2"
        spec["workloads"].append({"name": f"smoke.{name}", "config": conf,
                                  "traffic": name, "chips": 1,
                                  "why": "smoke"})
    for m in spec["per_layer"] + spec["end_to_end"]:
        if "workloads" not in m:
            continue
        for w in list(m["workloads"]):
            kind = next(x for x in spec["workloads"] if x["name"] == w)
            tag = {"chat": "smoke.smoke-chat",
                   "train-b256": "smoke.smoke-train"}[kind["traffic"]]
            m["workloads"].append(tag)
            if tag == "smoke.smoke-chat":
                m["workloads"] += [f"smoke.{n}" for n in (extra_traffic or {})]
    # the closed loop's own end-to-end metric, added as a new entry
    spec["end_to_end"].append({
        "name": "served_tokens_per_s", "unit": "tokens/s", "better": "higher",
        "bound": 0.05, "source": "host_clock",
        "workloads": ["smoke.smoke-long"]})
    _write(dst / "BENCHMARK.json", spec)
    return dst


def _write(path: pathlib.Path, obj) -> None:
    path.write_text(json.dumps(obj, indent=1))
