"""Topology-aware placement + microbatch pipeline execution (ISSUE 3).

Acceptance contract: the topology-aware packer yields strictly fewer
total transfer hops than the flat packer on llama3-8b (and no worse
stall); a partitioned schedule's per-partition op totals sum to
``count_ops``; ``Schedule.pipeline`` models fill/steady/drain with
per-link contention; partitioned programs are numerically identical to
``jax.jit``; the GPipe microbatch drivers (forward and per-stage-vjp
backward) reproduce full-batch results; Trainer/ServeEngine run the
partitioned plan end-to-end; the program-cache signature distinguishes
hierarchies (regression: tech/geometry were omitted).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs, mapper
from repro.configs.lenet5 import CONFIG as LENET_CONFIG
from repro.core import estimator
from repro.mapper import (ChipSpec, PlacementPolicy, TileSpec,
                          build_graph, build_schedule, default_hierarchy,
                          map_arch, map_lenet, partition, place,
                          total_transfer_hops)
from repro.mapper.hardware import curve_candidates, tile_curve
from repro.models import lenet
from repro.parallel import pipeline as pipe_mod


def _lenet_args(batch=4, seed=1):
    params = lenet.init_lenet(jax.random.PRNGKey(0), LENET_CONFIG)
    imgs = jax.random.normal(jax.random.PRNGKey(seed),
                             (batch, 28, 28, 1), jnp.float32)
    return params, imgs


# ---------------------------------------------------------------------------
# topology: curves, inter-chip legs, locality
# ---------------------------------------------------------------------------


def test_curves_visit_every_tile_and_stay_adjacent():
    chip = ChipSpec(tiles=64)
    for kind, order in curve_candidates(chip).items():
        assert sorted(order) == list(range(64)), kind
    for kind in ("snake", "hilbert"):
        order = tile_curve(chip, kind)
        for a, b in zip(order, order[1:]):
            ax, ay = chip.tile_xy(a)
            bx, by = chip.tile_xy(b)
            assert abs(ax - bx) + abs(ay - by) == 1, (kind, a, b)


def test_interchip_transfer_pays_mesh_legs():
    """S3 regression: a cross-chip move must cost more when its endpoints
    sit far from the chips' IO corners."""
    h = default_hierarchy("proposed")
    bits = 1 << 20
    spc = h.subarrays_per_chip
    corner_src = 0                                   # chip 0, tile 0
    far_src = (h.chip.tiles - 1) * h.tile.subarrays  # chip 0, far corner
    t_near, e_near = h.transfer_cost(bits, corner_src, spc)
    t_far, e_far = h.transfer_cost(bits, far_src, spc)
    assert t_far > t_near
    assert e_far > e_near
    assert h.hop_count(far_src, spc) > h.hop_count(corner_src, spc)
    # and the route crosses real shared links: mesh edges + the serdes
    links = h.route_links(far_src, spc)
    kinds = {l[0] for l in links}
    assert kinds == {"noc", "serdes"}


def test_affinity_placement_beats_flat_on_llama():
    """The locality acceptance bar: topology-aware packing must yield
    strictly fewer total producer->consumer NoC hops than flat node-order
    packing on llama3-8b, and no more stall."""
    aff = map_arch("llama3-8b", "serve", seq_len=32, batch=1)
    flat = map_arch("llama3-8b", "serve", seq_len=32, batch=1,
                    policy=PlacementPolicy(topology="flat"))
    assert aff.placement.curve != "rowmajor"
    assert aff.report.total_hops < flat.report.total_hops
    assert aff.report.stall_s <= flat.report.stall_s
    # the report's hop total is the placement-level objective
    assert aff.report.total_hops == total_transfer_hops(aff.graph,
                                                        aff.placement)


def test_affinity_strictly_reduces_stall_when_hops_dominate():
    """On a hop-latency-dominated machine (huge t_hop_s, one subarray per
    tile) fewer hops must turn into strictly less stall."""
    def f(x, ws, wl):
        h = jnp.tanh(x @ ws[0])
        for w in ws[1:]:
            h = jnp.tanh(h @ w)
        return h @ wl + x          # long skip edge back to the input

    k = jax.random.PRNGKey(0)
    x = jnp.zeros((1, 64))
    ws = [jnp.zeros((64, 64))] * 40
    wl = jnp.zeros((64, 64))
    hier = dataclasses.replace(
        default_hierarchy("proposed"),
        tile=TileSpec(subarrays=1),
        chip=ChipSpec(tiles=64, t_hop_s=1e-3))
    g = build_graph(f, x, ws, wl)
    from repro.mapper import schedule as sched_mod
    aff = sched_mod.build_schedule_from_graph(g, hierarchy=hier)
    flat = sched_mod.build_schedule_from_graph(
        g, hierarchy=hier, policy=PlacementPolicy(topology="flat"))
    assert aff.report.total_hops < flat.report.total_hops
    assert 0.0 < aff.report.stall_s < flat.report.stall_s


def test_lenet_single_tile_placement_unchanged_by_topology():
    """Everything on one tile: the curve must be a no-op."""
    sched = map_lenet("serve", batch=4)
    assert sched.report.n_tiles == 1
    p = sched.placement
    for np_ in p.node_placements.values():
        blocks = list(p.iter_blocks(np_.node))
        assert all(b.chip == 0 and b.tile == 0 for b in blocks)
        assert [b.subarray for b in blocks] == [
            b.subarray for b in np_.iter_blocks(p.hierarchy)]


def test_placement_blocks_carry_coordinates():
    sched = map_arch("llama3-8b", "serve", seq_len=32, batch=1)
    p = sched.placement
    nd = max(p.node_placements.values(), key=lambda n: n.n_subarrays)
    seen = set()
    for blk in p.iter_blocks(nd.node, replica=0):
        assert (blk.chip, blk.tile, blk.local) == \
            sched.hierarchy.locate(blk.subarray)
        assert blk.subarray not in seen     # curve mapping is injective
        seen.add(blk.subarray)


# ---------------------------------------------------------------------------
# signature / program cache (S1 regression)
# ---------------------------------------------------------------------------


def test_signature_distinguishes_hierarchies():
    """Regression: identical block grids on different tech / tile / chip
    geometries used to hash identically and collide in the program
    cache."""
    params, imgs = _lenet_args()
    g = build_graph(lenet.lenet_apply, params, imgs)
    base = place(g, default_hierarchy("proposed"))
    other_tech = place(g, default_hierarchy("floatpim"))
    big_tile = place(g, dataclasses.replace(
        default_hierarchy("proposed"), tile=TileSpec(subarrays=32)))
    fast_noc = place(g, dataclasses.replace(
        default_hierarchy("proposed"),
        chip=ChipSpec(noc_bits_per_s=1.024e12)))
    sigs = {base.signature(), other_tech.signature(),
            big_tile.signature(), fast_noc.signature()}
    assert len(sigs) == 4


def test_program_cache_misses_across_hierarchies():
    mapper.clear_program_cache()
    prog_a = mapper.compile_schedule(map_lenet("serve", batch=4))
    prog_b = mapper.compile_schedule(map_lenet("serve", batch=4,
                                               tech="floatpim"))
    assert prog_a is not prog_b
    assert mapper.program_cache_stats()["misses"] == 2
    mapper.clear_program_cache()


# ---------------------------------------------------------------------------
# partition(): balance, coverage, cut-awareness
# ---------------------------------------------------------------------------


def test_partition_totals_sum_to_count_ops():
    """Acceptance: per-partition op totals must sum to the estimator's
    independent count on the same fn."""
    for sched in (map_lenet("train", batch=8, partitions=4),
                  map_arch("llama3-8b", "serve", seq_len=32, batch=1,
                           partitions=2)):
        parts = sched.partitions
        counts = estimator.count_ops_jaxpr(sched.graph.closed_jaxpr.jaxpr)
        assert sum(p.macs for p in parts) == counts.macs
        assert sum(p.adds for p in parts) == counts.adds
        assert sum(p.muls for p in parts) == counts.muls
        covered = sorted(n for p in parts for n in p.nodes)
        assert covered == list(range(len(sched.graph.nodes)))


def test_partition_boundaries_contiguous_and_balanced():
    sched = map_lenet("train", batch=8)
    parts = partition(sched.graph, 4)
    assert parts[0].eqn_start == 0
    assert parts[-1].eqn_end == len(sched.graph.closed_jaxpr.jaxpr.eqns)
    for a, b in zip(parts, parts[1:]):
        assert a.eqn_end == b.eqn_start
        assert a.out_bits == b.in_bits > 0
    # balanced: no partition dominates the ideal bottleneck by > slack
    works = [p.work for p in parts]
    assert max(works) <= sum(works)        # sanity
    assert max(works) < 0.6 * sum(works)   # the lenet train step balances


def test_partition_clamps_to_top_level_eqns():
    def f(x, w):
        return x @ w

    g = build_graph(f, jnp.zeros((4, 8)), jnp.zeros((8, 8)))
    parts = partition(g, 5)
    assert len(parts) == len(g.closed_jaxpr.jaxpr.eqns)


def test_partition_alignment_when_first_node_is_eltwise():
    """Regression: a partition whose first graph node is eltwise (no
    placement) must still align its first *placed* node to a tile
    boundary — alignment keys on the partition transition, not on the
    literal first node."""
    from repro.mapper.placement import GraphPartition

    def f(x, w1, w2):
        h = x @ w1
        h = h + 1.0
        return h @ w2

    g = build_graph(f, jnp.zeros((4, 64)), jnp.zeros((64, 32)),
                    jnp.zeros((32, 32)))
    kinds = [nd.kind for nd in g.nodes]
    assert kinds == ["matmul", "eltwise", "matmul"]
    parts = [GraphPartition(idx=0, eqn_start=0, eqn_end=1, nodes=(0,),
                            macs=g.nodes[0].macs, adds=0, muls=0,
                            in_bits=0, out_bits=1),
             GraphPartition(idx=1, eqn_start=1, eqn_end=3, nodes=(1, 2),
                            macs=g.nodes[2].macs, adds=g.nodes[1].adds,
                            muls=0, in_bits=1, out_bits=0)]
    h = default_hierarchy("proposed")
    p = place(g, h, partitions=parts)
    per_tile = h.tile.subarrays
    assert p.node_placements[2].first_subarray % per_tile == 0
    assert p.node_placements[2].first_subarray > 0
    assert not p.node_placements[2].shared


def test_partition_aligned_placement_separates_stage_tiles():
    sched = map_lenet("train", batch=8, partitions=2)
    p = sched.placement
    per_tile = sched.hierarchy.tile.subarrays
    tiles_by_part = []
    for gp in sched.partitions:
        tiles = {p.coords(p.node_placements[n].first_subarray)[1]
                 for n in gp.nodes if n in p.node_placements}
        tiles_by_part.append(tiles)
    assert not (tiles_by_part[0] & tiles_by_part[1])
    # alignment costs at most one tile's worth of padding per boundary
    unaligned = map_lenet("train", batch=8)
    assert sched.report.n_subarrays <= (unaligned.report.n_subarrays
                                        + per_tile)


# ---------------------------------------------------------------------------
# pipeline timeline
# ---------------------------------------------------------------------------


def test_pipeline_timeline_fill_steady_drain():
    sched = map_lenet("train", batch=8, partitions=4)
    tl = sched.pipeline(8)
    assert tl.n_partitions == 4
    # interval is bounded below by the slowest partition and any link
    slowest = max(p.t_compute_s for p in tl.partitions)
    assert tl.interval_s >= slowest
    assert tl.interval_s >= tl.link_busy_s
    # makespan: fill + (M-1) intervals; sequential: M full latencies
    assert tl.makespan_s == pytest.approx(
        tl.fill_s + 7 * tl.interval_s)
    assert tl.sequential_s == pytest.approx(8 * sched.report.latency_s)
    # partitions cover the whole schedule's latency exactly
    assert sum(p.t_compute_s for p in tl.partitions) == pytest.approx(
        sched.report.latency_s)
    assert tl.speedup >= 1.5                # the acceptance bar workload
    assert "partition:" in tl.bottleneck or "link:" in tl.bottleneck


def test_pipeline_timeline_degenerate_single_partition():
    sched = map_lenet("serve", batch=4)
    tl = sched.pipeline(8, partitions=1)
    assert tl.n_partitions == 1
    assert tl.speedup == pytest.approx(1.0)


def test_pipeline_more_microbatches_amortize_fill():
    sched = map_lenet("train", batch=8, partitions=4)
    s2 = sched.pipeline(2).speedup
    s8 = sched.pipeline(8).speedup
    s64 = sched.pipeline(64).speedup
    assert s2 < s8 < s64


def test_reconciles_with_partitions():
    """Cutting the schedule must not break the estimator contract."""
    sched = map_lenet("train", batch=8, partitions=4)
    rec = sched.reconcile()
    assert rec["counts_match"] and rec["latency_ge_ideal"], rec


# ---------------------------------------------------------------------------
# partitioned programs: execution + gpipe drivers
# ---------------------------------------------------------------------------


def test_partitioned_program_matches_jit_lenet():
    params, imgs = _lenet_args()
    prog = mapper.compile_lenet("serve", batch=4, partitions=2)
    assert prog.n_partitions == 2
    assert prog.verify(params, imgs) < 1e-4
    assert prog.placed_blocks > 0
    assert prog.kernel_launches <= prog.placed_blocks + prog.eltwise_calls
    # explicit transfer points: stage 1 consumes stage 0's boundary
    assert any(r[0] == "stage" for r in prog.stages[1].in_refs)
    assert prog.stages[0].out_bits > 0


def test_gpipe_forward_matches_sequential():
    params, _ = _lenet_args()
    prog = mapper.compile_lenet("serve", batch=4, partitions=3)
    mbs = [jax.random.normal(jax.random.PRNGKey(m), (4, 28, 28, 1))
           for m in range(5)]
    flat_per_mb = [prog.flatten_args(params, im) for im in mbs]
    outs = pipe_mod.run_partitioned(prog.stages, prog.out_refs, flat_per_mb)
    for im, out in zip(mbs, outs):
        want = jax.jit(lenet.lenet_apply)(params, im)
        np.testing.assert_allclose(np.asarray(out[0]), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)


def test_gpipe_value_and_grad_matches_full_batch():
    """Per-stage-vjp GPipe backward == full-batch value_and_grad."""
    params, _ = _lenet_args()
    imgs = jax.random.normal(jax.random.PRNGKey(3), (8, 28, 28, 1))
    labels = jnp.array([1, 7, 3, 9, 0, 2, 5, 8], jnp.int32)
    n_micro = 4
    mb = 8 // n_micro
    sched = build_schedule(
        lenet.lenet_loss, mapper.abstract_like(params),
        jax.ShapeDtypeStruct((mb, 28, 28, 1), jnp.float32),
        jax.ShapeDtypeStruct((mb,), jnp.int32), partitions=2)
    prog = mapper.compile_partitioned(sched, use_cache=False)
    flat_per_mb = [
        prog.flatten_args(params, imgs[m * mb:(m + 1) * mb],
                          labels[m * mb:(m + 1) * mb])
        for m in range(n_micro)]
    n_param = len(jax.tree.leaves(params))
    loss, gflat = pipe_mod.gpipe_value_and_grad(
        prog.stages, prog.out_refs[0], flat_per_mb, list(range(n_param)))
    want_loss, want_grads = jax.value_and_grad(lenet.lenet_loss)(
        params, imgs, labels)
    np.testing.assert_allclose(float(loss), float(want_loss),
                               rtol=1e-5, atol=1e-6)
    grads = jax.tree.unflatten(jax.tree.structure(params), gflat)
    for g, w in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# end to end: Trainer / ServeEngine run the partitioned plan
# ---------------------------------------------------------------------------


def test_trainer_microbatch_pipeline_matches_jit(tmp_path):
    """The headline acceptance criterion: Trainer(backend='pim',
    microbatches=8, partitions=2) losses match the jit backend."""
    from repro.data import DigitsDataset
    from repro.optim import make_optimizer
    from repro.train import Trainer, TrainerConfig

    opt = make_optimizer("adamw", lr=2e-3)
    ds = DigitsDataset(batch_size=32, seed=0)

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

    def make(sub, backend, **kw):
        tc = TrainerConfig(total_steps=6, ckpt_every=50,
                           ckpt_dir=str(tmp_path / sub), async_ckpt=False)
        return Trainer(tc, train_step=train_step, init_state=init_state,
                       batch_fn=ds.batch, backend=backend, **kw)

    tr = make("pipe", "pim", microbatches=8, partitions=2,
              loss_fn=loss_fn, optimizer=opt)
    res = tr.run()
    assert tr.pim_program is not None
    assert tr.pim_program.n_partitions == 2
    traced = tr.pim_program.stage_trace_count
    assert traced == 8 * 2                 # one outer trace: M x K bodies
    res_jit = make("jit", "jit").run()
    np.testing.assert_allclose(res["losses"], res_jit["losses"],
                               rtol=1e-4, atol=1e-5)
    # zero retrace after warmup: 6 steps, still one outer trace
    assert tr.pim_program.stage_trace_count == traced


def test_trainer_knobs_validated(tmp_path):
    from repro.train import Trainer, TrainerConfig

    tc = TrainerConfig(total_steps=1, ckpt_dir=str(tmp_path))
    with pytest.raises(ValueError, match="backend='pim'"):
        Trainer(tc, train_step=lambda *a: a, init_state=lambda: ({}, {}),
                batch_fn=lambda s: (), backend="jit", microbatches=4)


def test_serve_engine_partitioned_matches_jit():
    from repro.serve import Request, ServeEngine

    cfg = configs.get_smoke_config("llama3-8b")
    from repro.models.transformer import build_model
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, 3 + i, dtype=np.int32)
               for i in range(3)]

    def drive(backend, **kw):
        eng = ServeEngine(cfg, params, batch=2, max_len=64,
                          backend=backend, **kw)
        for i, p in enumerate(prompts):
            eng.submit(Request(rid=i, prompt=p, max_tokens=4))
        return eng, {r.rid: r.out for r in eng.run()}

    eng_jit, out_jit = drive("jit")
    eng_pim, out_pim = drive("pim", partitions=2, microbatches=8)
    assert out_jit == out_pim
    assert eng_pim.pim_program.n_partitions == 2
    tl = eng_pim.pipeline_timeline
    assert tl is not None and tl.microbatches == 8
    assert tl.makespan_s >= tl.fill_s
    # the dead per-slot position array is gone (S2)
    assert not hasattr(eng_pim, "pos")
