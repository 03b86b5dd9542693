"""Scan residency + device-backed async pipeline execution.

Covers the two halves of the "cut the scan" feature: (1) expanding a
``repeat=R`` scanned layer stack into resident per-layer copies —
expanded-vs-scanned graph equivalence (op totals, weight footprint,
numerics), partition cuts landing *inside* the stack, capacity-bucketed
expansion refusing past the subarray budget, and ``reconcile()`` holding
on expanded schedules; (2) the async GPipe driver over device-pinned
stage programs — bit-exact loss/token parity with sequential chaining on
lenet5 and the llama3-8b smoke decode, plus the modeled-vs-measured
``obs.pipeline_drift`` join.

Device pinning rides whatever ``jax.devices()`` offers: with
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (exported by CI)
each stage gets its own host device; on a single-device host the ring
wraps and the async path still runs — parity is asserted either way,
never skipped.
"""

import jax
from jax.extend.core import jaxpr_as_fun
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs, mapper, obs
from repro.core import estimator
from repro.mapper.graph import plan_scan_expansion, scan_lengths
from repro.models.transformer import build_model
from repro.parallel import pipeline as pipe_mod


@pytest.fixture(scope="module")
def llama():
    cfg = configs.get_smoke_config("llama3-8b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _stack_fn(n_layers=4, d=16):
    """A scanned MLP stack: scan over [R, d, d] weights, like the
    transformer stacks lower (one top-level scan eqn, repeat=R)."""

    def fn(ws, x):
        def body(h, w):
            return jnp.tanh(h @ w), ()
        h, _ = jax.lax.scan(body, x, ws)
        return h

    ws = jax.random.normal(jax.random.PRNGKey(0), (n_layers, d, d))
    x = jax.random.normal(jax.random.PRNGKey(1), (8, d))
    return fn, ws, x


def _device_ring(k: int) -> list:
    devs = jax.devices()
    return [devs[i % len(devs)] for i in range(k)]


# ---------------------------------------------------------------------------
# expansion: equivalence, cuts inside the stack, bucketing, reconcile
# ---------------------------------------------------------------------------


def test_expanded_graph_matches_scanned_totals_and_numerics():
    fn, ws, x = _stack_fn(n_layers=4, d=16)
    g = mapper.build_graph(fn, ws, x)
    assert scan_lengths(g.closed_jaxpr), "stack should lower to a scan"
    ex = mapper.expand_graph(g, weight_rows=1000, weight_cols=32,
                             budget=10**9)
    assert ex is not g and not scan_lengths(ex.closed_jaxpr)

    # op totals identical: R copies counting once each == one copy x R
    assert ex.totals() == g.totals()
    c_g = estimator.count_ops_jaxpr(g.closed_jaxpr.jaxpr)
    c_ex = estimator.count_ops_jaxpr(ex.closed_jaxpr.jaxpr)
    assert c_ex == c_g
    # resident weight footprint grows R-fold: each copy now *holds* its
    # layer's slice instead of streaming it through one shared grid
    assert ex.weight_values() == 4 * g.weight_values()
    # ... spread over one resident matmul node per layer
    assert len(ex.matmul_like()) == 4 * len(g.matmul_like())
    assert all(nd.repeat == 1 for nd in ex.matmul_like())

    # numerics bit-exact: the expanded jaxpr replays the same primitives
    want = jax.jit(fn)(ws, x)
    got = jaxpr_as_fun(ex.closed_jaxpr)(ws, x)[0]
    np.testing.assert_array_equal(np.asarray(want), np.asarray(got))


def test_partition_cuts_inside_expanded_stack():
    fn, ws, x = _stack_fn(n_layers=4, d=16)
    g = mapper.build_graph(fn, ws, x)
    # unexpanded: the scan is one uncuttable unit — asking for 4 yields
    # a degenerate cut dominated by one monolithic partition
    base = mapper.partition(g, 4)
    base_bottleneck = max(p.work for p in base)
    total = sum(p.work for p in base)
    assert base_bottleneck == total  # whole stack in one partition

    ex = mapper.expand_graph(g, weight_rows=1000, weight_cols=32,
                             budget=10**9)
    parts = mapper.partition(ex, 4)
    assert len(parts) == 4
    # cuts landed between the resident copies: balanced, not monolithic
    assert max(p.work for p in parts) < total
    assert max(p.work for p in parts) <= total / 4 * 2


def test_bucketed_expansion_respects_budget():
    fn, ws, x = _stack_fn(n_layers=8, d=16)
    g = mapper.build_graph(fn, ws, x)
    # one 16x16 layer at weight_rows=8, weight_cols=8 -> 4 blocks/copy;
    # base residency (the scanned copy) = 4 blocks
    copy_blocks = 4

    # budget for base + 3 extra copies -> n_copies=4, g=ceil(8/4)=2
    plan = plan_scan_expansion(g, weight_rows=8, weight_cols=8,
                               budget=copy_blocks * 4)
    (gval,) = plan.values()
    assert gval == 2
    ex = mapper.expand_graph(g, weight_rows=8, weight_cols=8,
                             budget=copy_blocks * 4)
    # ceil(R/g)=4 resident copies, each a chunked scan of length 2
    assert len(ex.matmul_like()) == 4
    assert all(nd.repeat == 2 for nd in ex.matmul_like())
    assert ex.totals() == g.totals()

    # budget below two resident copies: refuse — graph returned unchanged
    assert plan_scan_expansion(g, weight_rows=8, weight_cols=8,
                               budget=copy_blocks) == {}
    assert mapper.expand_graph(g, weight_rows=8, weight_cols=8,
                               budget=copy_blocks) is g


@pytest.mark.parametrize("arch,kind", [("llama3-8b", "serve"),
                                       ("qwen2.5-32b", "serve")])
def test_reconcile_holds_on_expanded_arch(arch, kind):
    sched = mapper.map_arch(arch, kind, smoke=True, expand_scans=True)
    r = sched.reconcile()
    assert r["counts_match"] and r["latency_ge_ideal"]
    # the tentpole number: cuts inside the stack lift the modeled
    # pipeline speedup well past the old uncuttable-monolith ~1x
    assert sched.pipeline(8, partitions=4).speedup >= 2.0


def test_reconcile_holds_on_expanded_lenet():
    sched = mapper.map_lenet("train", expand_scans=True)
    r = sched.reconcile()
    assert r["counts_match"] and r["latency_ge_ideal"]


# ---------------------------------------------------------------------------
# async device-backed driver: parity with sequential chaining
# ---------------------------------------------------------------------------


def test_async_driver_matches_sequential_lenet():
    from repro.configs.lenet5 import CONFIG
    from repro.models import lenet

    params = lenet.init_lenet(jax.random.PRNGKey(0), CONFIG)
    x = jax.random.normal(jax.random.PRNGKey(1),
                          (4, CONFIG.in_hw, CONFIG.in_hw, 1))
    ring = _device_ring(4)
    pinned = mapper.compile_lenet("serve", partitions=4, devices=ring)
    plain = mapper.compile_lenet("serve", partitions=4)
    assert pinned.devices == tuple(ring)
    assert plain.devices == (None,) * 4

    # whole-chain async vs jitted sequential chain
    seq = pinned(params, x)
    asy = pinned.run_async(params, x)
    for a, b in zip(jax.tree.leaves(seq), jax.tree.leaves(asy)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # GPipe grid: async pinned vs sequential unpinned, 8 microbatches
    mbs = [plain.flatten_args(params, x) for _ in range(8)]
    o_seq = pipe_mod.run_partitioned(plain.stages, plain.out_refs, mbs)
    o_asy = pipe_mod.run_partitioned_async(pinned.stages, pinned.out_refs,
                                           mbs)
    for r_seq, r_asy in zip(o_seq, o_asy):
        for a, b in zip(r_seq, r_asy):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_async_decode_token_parity_llama_smoke(llama):
    from repro.serve.engine import Request, ServeEngine

    cfg, model, params = llama
    prompts = [np.arange(1, 5, dtype=np.int32),
               np.arange(3, 9, dtype=np.int32)]

    def run(pim_compile):
        eng = ServeEngine(cfg, params, batch=2, max_len=16, backend="pim",
                          partitions=4, expand_scans=True,
                          pim_compile=pim_compile)
        for i, p in enumerate(prompts):
            eng.submit(Request(rid=i, prompt=p, max_tokens=4))
        eng.run()
        return [tuple(r.out) for r in sorted(eng.completed,
                                             key=lambda r: r.rid)], eng

    toks_seq, eng_seq = run(None)
    toks_asy, eng_asy = run({"devices": _device_ring(4)})
    assert toks_asy == toks_seq
    assert eng_seq.pim_program.n_partitions == 4
    # the async engine decodes through the device-routed chain
    assert any(d is not None for d in eng_asy.pim_program.devices)
    assert eng_asy._decode == eng_asy.pim_program.run_async


def test_trainer_async_pipeline_matches_sequential(tmp_path):
    from repro.configs.lenet5 import CONFIG as LENET_CONFIG
    from repro.data import DigitsDataset
    from repro.models import lenet
    from repro.optim import make_optimizer
    from repro.train import Trainer, TrainerConfig

    opt = make_optimizer("adamw", lr=2e-3)
    ds = DigitsDataset(batch_size=16, seed=0)

    def init_state():
        p = lenet.init_lenet(jax.random.PRNGKey(0), LENET_CONFIG)
        return p, opt.init(p)

    def loss_fn(params, imgs, labels):
        return lenet.lenet_loss(params, jnp.asarray(imgs),
                                jnp.asarray(labels))

    def train_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, *batch)
        params, opt_state = opt.update(grads, opt_state, params)
        return params, opt_state, loss

    def make(sub, pim_compile):
        tc = TrainerConfig(total_steps=3, ckpt_every=50,
                           ckpt_dir=str(tmp_path / sub), async_ckpt=False)
        return Trainer(tc, train_step=train_step, init_state=init_state,
                       batch_fn=ds.batch, backend="pim", microbatches=4,
                       partitions=2, loss_fn=loss_fn, optimizer=opt,
                       pim_compile=pim_compile)

    t_seq = make("seq", None)
    t_asy = make("asy", {"devices": _device_ring(2)})
    # pinned stages keep the step eager (jit would erase the routing)
    assert all(d is not None for d in t_asy.pim_program.devices)
    r_seq = t_seq.run()
    r_asy = t_asy.run()
    np.testing.assert_allclose(r_asy["losses"], r_seq["losses"],
                               rtol=1e-6, atol=1e-7)


# ---------------------------------------------------------------------------
# modeled-vs-measured pipeline drift
# ---------------------------------------------------------------------------


def test_pipeline_drift_joins_async_spans():
    from repro.configs.lenet5 import CONFIG
    from repro.models import lenet

    params = lenet.init_lenet(jax.random.PRNGKey(0), CONFIG)
    x = jax.random.normal(jax.random.PRNGKey(1),
                          (4, CONFIG.in_hw, CONFIG.in_hw, 1))
    sched = mapper.map_lenet("serve", partitions=4)
    prog = mapper.compile_partitioned(sched, use_cache=False,
                                      devices=_device_ring(4))
    n_micro = 4
    mbs = [prog.flatten_args(params, x) for _ in range(n_micro)]
    with obs.scoped() as tr:
        pipe_mod.run_partitioned_async(prog.stages, prog.out_refs, mbs)
    timeline = sched.pipeline(n_micro)
    rep = obs.pipeline_drift(timeline, tr)
    assert rep.microbatches == n_micro
    assert len(rep.stages) == 4
    # every (stage, microbatch) cell was measured on its stage lane
    assert all(s.cells == n_micro for s in rep.stages)
    assert all(s.measured_s > 0 for s in rep.stages)
    # one device_put instant per cell with upstream inputs
    assert rep.transfers > 0
    assert rep.measured_interval_s > 0 and rep.ratio > 0
    assert "pipeline drift" in rep.summary()


def test_pipeline_drift_requires_spans():
    sched = mapper.map_lenet("serve", partitions=2)
    with obs.scoped() as tr:
        pass
    with pytest.raises(ValueError, match="no pipeline-lane"):
        obs.pipeline_drift(sched.pipeline(4), tr)
