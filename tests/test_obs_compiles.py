"""The compile record (``repro.obs.compile_record``): one entry per program
built, fed by JAX's compile events under the label open at the time,
recompiles flagged with their key, the mapper's phases in the same
record; and the benchmark's three set-up readers over a hand-made
record."""

import importlib.util
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import monitoring

from repro import configs, obs
from repro.models.transformer import build_model
from repro.obs import compile_record as rec
from repro.serve import Request, ServeEngine

METRICS = pathlib.Path(__file__).resolve().parents[1] / "bench" / "metrics"


@pytest.fixture(autouse=True)
def _fresh_record():
    obs.reset_compiles()
    yield
    obs.reset_compiles()


@pytest.fixture(scope="module")
def llama():
    cfg = configs.get_smoke_config("llama3-8b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _submit(eng, n, rid):
    rng = np.random.default_rng(rid)
    eng.submit(Request(rid=rid, max_tokens=2, prompt=rng.integers(
        0, eng.cfg.vocab_size, n, dtype=np.int32)))
    eng.run()


def _names():
    return [b.name for b in obs.compiles()]


def test_events_are_filed_under_the_open_label_exclusively():
    with obs.program("unit", 3):
        # an inner trace nested in the outer one, then lowering, then a
        # backend compile that read the program back from the cache
        monitoring.record_event_time_span(rec.TRACE_EVENT, 10.2, 10.5)
        monitoring.record_event_time_span(rec.TRACE_EVENT, 10.0, 11.0)
        monitoring.record_event_time_span(rec.LOWER_EVENT, 11.0, 11.5)
        monitoring.record_event_duration_secs(rec.RETRIEVAL_EVENT, 0.5)
        monitoring.record_event(rec.HIT_EVENT)
        monitoring.record_event_time_span(rec.BACKEND_EVENT, 11.5, 13.0)
    monitoring.record_event_time_span(rec.TRACE_EVENT, 20.0, 21.0)  # no label
    b, = obs.compiles()
    assert b.name == "unit[3]" and not b.recompile
    assert b.trace_s == pytest.approx(1.0)       # 0.3 inner + 0.7 outer
    assert b.lower_s == pytest.approx(0.5)
    assert b.backend_s == pytest.approx(1.5)
    assert b.retrieval_s == pytest.approx(0.5)
    assert b.compile_s == pytest.approx(1.0)
    assert b.cache_hit is True
    assert b.wall_s > 0


def test_program_builds_repeats_buckets_and_recompiles(llama):
    cfg, _, params = llama
    eng = ServeEngine(cfg, params, batch=2, max_len=32, paged=True,
                      kv_block_size=4, prefill="batch")
    _submit(eng, 6, 0)                  # 5 prompt tokens prefilled: bucket 8
    assert sorted(_names()) == ["serve.decode", "serve.prefill[8]"]
    for b in obs.compiles():
        assert b.trace_s > 0 and b.lower_s > 0 and b.backend_s > 0
        assert not b.recompile
        assert b.wall_s >= b.jax_s > 0
    _submit(eng, 7, 1)                  # same bucket, same decode: nothing
    assert len(obs.compiles()) == 2
    _submit(eng, 11, 2)                 # a new bucket: one entry
    assert _names()[2:] == ["serve.prefill[12]"]
    jax.jit(lambda x: x * 2 + 1)(jnp.ones(3))    # unlabelled: not recorded
    assert len(obs.compiles()) == 3
    jax.clear_caches()                  # force a retrace of built keys
    _submit(eng, 6, 3)
    again = obs.compiles()[3:]
    assert sorted(b.name for b in again) == ["serve.decode",
                                             "serve.prefill[8]"]
    assert all(b.recompile for b in again)
    assert {b.key for b in again} == {None, 8}


def test_mapper_phases_are_recorded_with_their_own_seconds(llama):
    cfg, _, params = llama
    ServeEngine(cfg, params, batch=2, max_len=32, paged=True,
                kv_block_size=4, prefill="batch", backend="pim")
    phases = {b.label: b for b in obs.compiles()
              if b.label.startswith("mapper.")}
    assert set(phases) == {"mapper.build_schedule", "mapper.place_kv",
                           "mapper.compile_schedule"}
    for b in phases.values():
        assert b.wall_s > 0
        assert b.mapper_s == pytest.approx(b.wall_s - b.jax_s)
    # the schedule traces its fn to a jaxpr: JAX time, not mapper time
    build = phases["mapper.build_schedule"]
    assert build.trace_s > 0 and build.mapper_s < build.wall_s
    # nothing is jitted before the first call
    assert not any(b.label.startswith("serve.") for b in obs.compiles())


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        f"reader_{name}", METRICS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_setup_readers_sum_a_hand_made_record(monkeypatch):
    record = [
        rec.Build("serve.decode", trace_s=1.0, lower_s=2.0, backend_s=3.0,
                  retrieval_s=0.5, cache_hit=True, wall_s=6.5),
        rec.Build("serve.prefill", 8, trace_s=0.25, lower_s=0.5,
                  backend_s=1.0, cache_hit=False, wall_s=2.0),
        rec.Build("mapper.build_schedule", trace_s=0.125, wall_s=4.125,
                  mapper_s=4.0),
        rec.Build("mapper.place_kv", wall_s=0.75, mapper_s=0.75),
    ]
    monkeypatch.setattr(obs, "compiles", lambda: list(record))
    assert _reader("setup_trace_s").read(None) == pytest.approx(3.875)
    assert _reader("setup_compile_s").read(None) == pytest.approx(4.0)
    assert _reader("setup_mapper_s").read(None) == pytest.approx(4.75)
    # a program that keeps no record: nothing to read, nothing raised
    monkeypatch.delattr(obs, "compiles")
    for name in ("setup_trace_s", "setup_compile_s", "setup_mapper_s"):
        assert _reader(name).read(None) is None
