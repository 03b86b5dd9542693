"""Observability: tracer/metrics primitives, Chrome-trace export and
validation, drift reports on the llama3-8b smoke schedules (train step
and paged serve), and the zero-cost contract when disabled (no
retraces, <5% wall overhead)."""

import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs, mapper, obs
from repro.models.transformer import build_model
from repro.serve import Request, ServeEngine


@pytest.fixture(autouse=True)
def _disabled_tracer():
    """Every test starts and ends with observability off."""
    obs.disable()
    yield
    obs.disable()


@pytest.fixture(scope="module")
def llama():
    cfg = configs.get_smoke_config("llama3-8b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


# ---------------------------------------------------------------------------
# tracer + metrics primitives
# ---------------------------------------------------------------------------


def test_span_nesting_lanes_and_chrome_roundtrip(tmp_path):
    tr = obs.Tracer()
    with tr.span("outer", lane="x", a=1):
        with tr.span("inner", lane="x"):
            pass
        tr.instant("mark", lane="x")
    with tr.span("other", lane="y"):
        pass
    assert tr.lanes() == ["x", "y"]
    assert len(tr.spans(lane="x")) == 2
    inner, = tr.spans(name="inner")
    outer, = tr.spans(name="outer")
    assert inner.depth == 1 and outer.depth == 0
    assert outer.t0_s <= inner.t0_s and inner.t1_s <= outer.t1_s

    path = tmp_path / "t.trace.json"
    tr.export_chrome(path)
    lanes = obs.validate_chrome_trace(path)       # re-loads from disk
    assert lanes == {"x": 2, "y": 1}
    # instants survive as ph="i" events
    data = json.loads(path.read_text())
    phases = {e["ph"] for e in data["traceEvents"]}
    assert phases == {"M", "X", "i"}


def test_validate_rejects_overlap_and_unnamed_lanes():
    bad = {"traceEvents": [
        {"ph": "M", "pid": 0, "tid": 0, "name": "thread_name",
         "args": {"name": "x"}},
        {"ph": "X", "pid": 0, "tid": 0, "name": "a", "ts": 0.0, "dur": 10.0},
        {"ph": "X", "pid": 0, "tid": 0, "name": "b", "ts": 5.0, "dur": 10.0},
    ]}
    with pytest.raises(ValueError, match="without nesting"):
        obs.validate_chrome_trace(bad)
    unnamed = {"traceEvents": [
        {"ph": "X", "pid": 0, "tid": 7, "name": "a", "ts": 0.0, "dur": 1.0}]}
    with pytest.raises(ValueError, match="thread_name"):
        obs.validate_chrome_trace(unnamed)


def test_null_tracer_and_scoped_restore():
    assert not obs.is_enabled()
    assert obs.tracer() is obs.NULL_TRACER
    # the disabled span is one shared no-op context manager
    cm1 = obs.tracer().span("a", lane="x", big=list(range(3)))
    cm2 = obs.tracer().span("b")
    assert cm1 is cm2
    with obs.scoped() as tr:
        assert obs.is_enabled() and obs.tracer() is tr
        with obs.span("w", lane="z"):
            pass
    assert not obs.is_enabled()
    assert len(tr.spans(lane="z")) == 1


def test_metrics_registry_instruments():
    reg = obs.MetricsRegistry()
    reg.counter("c").inc()
    reg.counter("c").inc(2)
    assert reg.counter("c").value == 3
    with pytest.raises(ValueError):
        reg.counter("c").inc(-1)
    reg.gauge("g").set(7)
    h = reg.histogram("h")
    for v in (0.001, 0.002, 0.003, 0.004):
        h.observe(v)
    snap = reg.snapshot()
    assert snap["counters"]["c"] == 3 and snap["gauges"]["g"] == 7
    assert snap["histograms"]["h"]["count"] == 4
    assert snap["histograms"]["h"]["p50"] == pytest.approx(0.0025)
    with pytest.raises(ValueError, match="different edges"):
        reg.histogram("h", edges=(1.0, 2.0))
    reg.reset()
    assert reg.snapshot()["counters"] == {}


# ---------------------------------------------------------------------------
# llama3-8b smoke train step: trace + drift
# ---------------------------------------------------------------------------


def test_llama_train_step_trace_and_drift(tmp_path, llama):
    cfg, model, params = llama
    tok = jnp.array([[3, 5, 2, 9]], jnp.int32)

    def train_step(params, tok):
        def loss_fn(p):
            return jnp.mean(model.apply(p, tokens=tok) ** 2)
        loss, grads = jax.value_and_grad(loss_fn)(params)
        new = jax.tree.map(lambda p, g: p - 1e-3 * g, params, grads)
        return new, loss

    sched = mapper.build_schedule(train_step, mapper.abstract_like(params),
                                  mapper.abstract_like(tok))
    with obs.scoped() as tr:
        mapper.ScheduleExecutor(sched).run(params, tok)
    report = obs.drift_report(sched, tr)
    assert report.n_measured > 0
    assert report.measured_total_s > 0 and report.modeled_total_s > 0
    # interpret-mode emulation runs far above the modeled hardware time
    # in aggregate (individual nodes can model slower than they emulate)
    assert report.ratio > 1
    assert report.by_ratio()[0].ratio > 1
    assert all(n.measured_s > 0 for n in report.by_ratio())
    assert f"[{sched.report.tech}] drift" in report.summary()
    drift_path = tmp_path / "train.drift.json"
    report.export_json(drift_path)
    loaded = json.loads(drift_path.read_text())
    assert loaded["nodes"] and loaded["ratio"] == pytest.approx(report.ratio)

    trace_path = tmp_path / "train.trace.json"
    tr.export_chrome(trace_path)
    lanes = obs.validate_chrome_trace(trace_path)
    assert "execute" in lanes and lanes["execute"] >= report.n_measured
    # every node launch span nests under the depth-0 run span
    run, = tr.spans(lane="execute", name="run:schedule")
    for s in tr.spans(lane="execute"):
        assert run.t0_s <= s.t0_s and s.t1_s <= run.t1_s + 1e-9


def test_measure_drift_one_shot():
    def f(x, w):
        return x @ w

    sched = mapper.build_schedule(f, jax.ShapeDtypeStruct((8, 16),
                                                          jnp.float32),
                                  jax.ShapeDtypeStruct((16, 8), jnp.float32))
    report = obs.measure_drift(sched, jnp.ones((8, 16)), jnp.ones((16, 8)))
    assert report.n_measured == 1 and len(report.nodes) == 1
    assert report.nodes[0].kind == "matmul" and report.nodes[0].launches == 1
    assert not obs.is_enabled()       # scoped tracer was restored


def test_drift_report_requires_spans():
    def f(x, w):
        return x @ w

    sched = mapper.build_schedule(f, jax.ShapeDtypeStruct((8, 16),
                                                          jnp.float32),
                                  jax.ShapeDtypeStruct((16, 8), jnp.float32))
    with pytest.raises(ValueError, match="no execute-lane spans"):
        obs.drift_report(sched, obs.Tracer())


# ---------------------------------------------------------------------------
# paged serve: trace + drift + TTFT/TPOT histograms
# ---------------------------------------------------------------------------


def test_paged_serve_trace_drift_and_latency_histograms(tmp_path, llama):
    cfg, model, params = llama
    rng = np.random.default_rng(0)
    obs.metrics().reset()
    eng = ServeEngine(cfg, params, batch=2, max_len=32, paged=True,
                      kv_block_size=4, backend="pim")
    for i in range(3):
        prompt = rng.integers(0, cfg.vocab_size, 3 + i, dtype=np.int32)
        eng.submit(Request(rid=i, prompt=prompt, max_tokens=3))
    with obs.scoped() as tr:
        done = eng.run()
    assert len(done) == 3

    trace_path = tmp_path / "serve.trace.json"
    tr.export_chrome(trace_path)
    lanes = obs.validate_chrome_trace(trace_path)
    assert "serve" in lanes and "execute" in lanes
    assert len(tr.spans(lane="serve", name="decode:tick")) > 0
    admits = tr.spans(lane="serve", name="admit")
    assert len(admits) == 3
    assert sorted(e.args["rid"] for e in admits) == [0, 1, 2]

    # the engine's drift report joins the program:call spans against the
    # pim schedule's modeled decode cost
    report = eng.drift_report(tr)
    assert report.measured_total_s > 0 and len(report.nodes) > 0
    assert report.ratio > 1

    # per-node ratios come from one eager oracle run of the same schedule
    feed = np.zeros(eng.batch, np.int32)
    node_report = obs.measure_drift(
        eng.schedule, eng.params, eng.cache, jnp.asarray(feed),
        eng.kv.device_table(), jnp.asarray(eng._pos))
    assert node_report.n_measured > 0
    assert node_report.ratio > 1
    assert node_report.by_ratio()[0].ratio > 1

    snap = obs.metrics().snapshot()
    assert snap["counters"]["serve.submitted"] == 3
    assert snap["counters"]["serve.completed"] == 3
    assert snap["histograms"]["serve.ttft_s"]["count"] == 3
    assert snap["histograms"]["serve.tpot_s"]["count"] == 3
    for r in done:
        assert r.ttft_s is not None and r.ttft_s > 0
        assert r.tpot_s is not None and r.tpot_s > 0
    metrics_path = tmp_path / "serve.metrics.json"
    obs.metrics().export_json(metrics_path)
    assert json.loads(metrics_path.read_text())["counters"]


def test_drift_report_requires_pim_backend(llama):
    cfg, model, params = llama
    eng = ServeEngine(cfg, params, batch=2, max_len=32, paged=True,
                      kv_block_size=4)
    with pytest.raises(ValueError, match="backend='pim'"):
        eng.drift_report()


# ---------------------------------------------------------------------------
# zero-cost when disabled: no retraces, <5% wall overhead
# ---------------------------------------------------------------------------


def test_disabled_obs_adds_no_retraces(llama):
    cfg, model, params = llama
    cache = model.init_cache(2, 16)
    tok = jnp.array([3, 5], jnp.int32)

    def decode(params, cache, tok, pos):
        return model.decode_step(params, cache, tok, pos)

    sched = mapper.build_schedule(decode, mapper.abstract_like(params),
                                  mapper.abstract_like(cache),
                                  mapper.abstract_like(tok),
                                  jax.ShapeDtypeStruct((), jnp.int32))
    prog = mapper.compile_schedule(sched, use_cache=False)
    jax.block_until_ready(prog(params, cache, tok, jnp.int32(0)))
    assert prog.trace_count == 1
    # calls through the instrumented wrapper — disabled and enabled —
    # reuse the warm jit executable: zero retraces either way
    prog(params, cache, tok, jnp.int32(1))
    with obs.scoped():
        prog(params, cache, tok, jnp.int32(2))
    prog(params, cache, tok, jnp.int32(3))
    assert prog.trace_count == 1


def test_disabled_obs_wall_overhead_under_5pct(llama):
    cfg, model, params = llama
    cache = model.init_cache(2, 16)
    tok = jnp.array([3, 5], jnp.int32)

    def decode(params, cache, tok, pos):
        return model.decode_step(params, cache, tok, pos)

    sched = mapper.build_schedule(decode, mapper.abstract_like(params),
                                  mapper.abstract_like(cache),
                                  mapper.abstract_like(tok),
                                  jax.ShapeDtypeStruct((), jnp.int32))
    prog = mapper.compile_schedule(sched, use_cache=False)
    args = (params, cache, tok, jnp.int32(0))
    jax.block_until_ready(prog(*args))                       # warm up

    def timed(fn):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        return time.perf_counter() - t0

    assert not obs.is_enabled()
    # interleaved min-of-N, so a burst of load on a shared host hits
    # both sides alike
    raw = instrumented = float("inf")
    for _ in range(15):
        raw = min(raw, timed(prog.jitted))   # the uninstrumented dispatch
        instrumented = min(instrumented, timed(prog))  # obs disabled
    # min-of-N on a ms-scale step: the disabled wrapper is one attribute
    # check, so anything above 5% would mean instrumentation leaked into
    # the hot path
    assert instrumented <= raw * 1.05 + 1e-4, (instrumented, raw)
