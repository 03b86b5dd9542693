"""``chip_smoke.py``'s control flow on the CPU: its serve and train phases
at smoke widths (Pallas kernels interpreted), and its refusal to run
anywhere but on a TPU."""

import pathlib
import sys

import jax
import pytest
from helpers import run_with_devices

from repro import configs

ROOT = str(pathlib.Path(__file__).resolve().parent.parent)
sys.path.insert(0, ROOT)
import chip_smoke  # noqa: E402


def test_main_refuses_a_cpu_platform(capsys):
    assert jax.devices()[0].platform == "cpu"
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out


def test_qwen_cut_keeps_published_widths():
    cut, full = chip_smoke.qwen_cut(2), configs.get_config("qwen2.5-32b")
    assert (cut.d_model, cut.n_heads, cut.n_kv_heads, cut.d_ff,
            cut.resolved_head_dim, cut.qkv_bias, cut.rope_theta) == (
        full.d_model, full.n_heads, full.n_kv_heads, full.d_ff,
        full.resolved_head_dim, full.qkv_bias, full.rope_theta)
    assert cut.n_layers == 2 and cut.vocab_size == 19008


def test_serve_phase_at_smoke_widths():
    cfg = configs.get_smoke_config("qwen2.5-32b")
    res = chip_smoke.serve_phase(cfg, batch=3, prompt_lens=(9, 20),
                                 new_tokens=3, block_size=4)
    assert res["logits_rel_dev"] <= chip_smoke.SERVE_LOGITS_TOL
    assert res["argmax_agree"] == 3
    assert res["placed_blocks"] > 0
    assert min(res["prompt_tokens"]) >= 9 and max(res["prompt_tokens"]) <= 20
    # interpreted on the CPU: the kernels are loops, not custom calls
    assert "tpu_custom_call" not in res["hlo"]


def test_train_phase_at_lenet_size():
    res = chip_smoke.train_phase(steps=2, batch=8)
    assert res["loss_rel_dev"] <= chip_smoke.TRAIN_LOSS_RTOL
    assert res["param_rel_dev"] <= chip_smoke.TRAIN_PARAM_RTOL
    assert len(res["param_rel_dev_by_leaf"]) == 10       # 5 layers x (w, b)
    assert len(res["pim_losses"]) == len(res["jit_losses"]) == 2


def test_main_refuses_cpu_with_four_chips():
    assert chip_smoke.main(["--four-chips"]) != 0


_PIPELINE = r"""
import dataclasses, sys
sys.path.insert(0, ROOT)
import jax
import chip_smoke
from repro import configs

cfg = dataclasses.replace(configs.get_smoke_config("qwen2.5-32b"), n_layers=4)
res = chip_smoke.pipeline_phase(cfg, jax.devices()[:4], prompt_len=12,
                                new_tokens=3, block_size=4)
assert res["logits_rel_dev"] <= chip_smoke.SERVE_LOGITS_TOL, res
assert res["partitions"] == 4 and len(set(res["stage_devices"])) == 4, res
print("PIPELINE_OK")
"""


def test_pipeline_phase_on_four_host_devices():
    """The ``--four-chips`` phase's control flow on 4 forced CPU devices:
    every stage's outputs on its own device, pinned == unpinned."""
    res = run_with_devices(_PIPELINE.replace("ROOT", repr(ROOT)),
                           n_devices=4, timeout=400)
    assert "PIPELINE_OK" in res.stdout, res.stdout + res.stderr


@pytest.mark.parametrize("env_dir", [None, "given"])
def test_compile_cache_location(tmp_path, monkeypatch, env_dir):
    """``JAX_COMPILATION_CACHE_DIR`` wins and nothing else is set;
    without it the cache goes to the fixed ``<root>/.jax_cache``."""
    from repro.launch.cache import use_compile_cache

    was = jax.config.jax_compilation_cache_dir
    min_s = jax.config.jax_persistent_cache_min_compile_time_secs
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = str(tmp_path.resolve() / ".jax_cache")
    else:
        want = str(tmp_path / env_dir)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)
    try:
        assert use_compile_cache(tmp_path) == want
        assert jax.config.jax_compilation_cache_dir == (
            want if env_dir is None else was)
        assert use_compile_cache(tmp_path) == want      # same path again
        # short compiles (prefill buckets) are kept too
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          min_s)
