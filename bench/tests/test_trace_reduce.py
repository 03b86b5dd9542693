"""The trace reduction on a hand-made event list and on a small trace
recorded on the CPU."""

import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import trace_reduce as tr  # noqa: E402


def ev(name, s, e, **stats):
    return tr.Ev(name, s, e, stats)


OPS = [ev("%pim_matmul_grouped.3 = f32[1] custom-call(x)", 0, 40),
       ev("%fusion.2 = f32[1] fusion(y)", 30, 60),      # overlaps the first
       ev("%paged_decode_attention.1 = bf16[1] custom-call(z)", 100, 130),
       ev("%pim_matmul_grouped.7 = f32[1] custom-call(x)", 125, 150),
       ev("%copy.1 = f32[1] copy(q)", 300, 310)]
HOST = [ev("bench.window", 0, 400),
        ev("bench.tick", 0, 200), ev("bench.decode", 5, 95),
        ev("bench.sample", 150, 200), ev("bench.tick", 200, 400),
        ev("bench.admit", 210, 290)]


def test_union_merges_overlaps_and_touching_spans():
    assert tr.union([(5, 9), (0, 4), (3, 6), (9, 12), (20, 21)]) == [
        (0, 12), (20, 21)]


def test_busy_counts_overlapping_ops_once():
    # [0, 60) + [100, 150) + [300, 310)
    assert tr.busy_ns(OPS, 0, 400) == 60 + 50 + 10
    assert tr.busy_ns(OPS, 20, 110) == 40 + 10


def test_gaps_are_the_complement_of_busy_time():
    g = tr.gaps(OPS, 0, 400)
    assert g == [(60, 100), (150, 300), (310, 400)]
    assert sum(e - s for s, e in g) + tr.busy_ns(OPS, 0, 400) == 400


def test_gap_is_attributed_to_the_innermost_open_annotation():
    trace = tr.Trace(ops={"0": OPS}, modules={"0": []}, host=HOST)
    assert trace.window() == (0, 400)
    names = [tr.host_at(trace.host, (s + e) // 2)
             for s, e in tr.gaps(OPS, 0, 400)]
    assert names == ["bench.decode", "bench.admit", "bench.tick"]
    assert tr.host_at(trace.host, 250) == "bench.admit"
    assert tr.host_at(trace.host, 500) == "none"


def test_time_per_kernel_by_short_name():
    t = tr.by_name(OPS)
    assert t["pim_matmul_grouped"] == 40 + 25
    assert t["paged_decode_attention"] == 30
    assert t["fusion"] == 30 and t["copy"] == 10
    assert [tr.is_custom_call(o) for o in OPS] == [True, False, True,
                                                   True, False]


def test_events_inside_spans_and_module_names():
    mods = [ev("jit_fn(8704382391234674163)", 0, 70),
            ev("jit_prefill_paged(12)", 95, 160)]
    assert [tr.module_name(m) for m in mods] == ["jit_fn",
                                                 "jit_prefill_paged"]
    inner = tr.inside(OPS, [(m.start, m.end) for m in mods])
    assert [tr.op_name(o) for o in inner] == [
        "pim_matmul_grouped", "fusion", "paged_decode_attention",
        "pim_matmul_grouped"]
    assert tr.in_window(OPS, 0, 150) == OPS[:4]


def test_reads_a_trace_recorded_on_the_cpu(tmp_path):
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.step", step=1):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    trace = tr.read(str(tmp_path))
    lo, hi = trace.window()
    steps = [e for e in trace.host if e.name == "bench.step"]
    assert len(steps) == 3 and all(e.stats["step"] == 1 for e in steps)
    assert all(lo <= e.start and e.end <= hi for e in steps)
    ops = trace.ops["cpu"]
    assert ops and 0 < tr.busy_ns(ops, lo, hi) <= hi - lo
    assert "jit__lambda" in {tr.module_name(m) for m in trace.modules["cpu"]}
