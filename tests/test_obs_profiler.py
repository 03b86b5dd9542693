"""The program's spans on the profiler clock: a paged serve run and a
LeNet train run under ``jax.profiler.trace`` leave the serve, train and
compile lanes' spans, with their args and nesting, in the ``.xplane.pb``;
with the tracer and the profiler both off no annotation is made and
nothing syncs; and the lowered programs do not depend on either."""

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro import configs, obs
from repro.configs.lenet5 import CONFIG as LENET_CONFIG
from repro.models import lenet
from repro.models.transformer import build_model
from repro.obs import trace as obs_trace
from repro.serve import Request, ServeEngine

SERVE_SPANS = {"tick", "admit", "prefill:batch", "decode:tick",
               "sample:sync"}
TRAIN_SPANS = {"train:step", "train:batch", "train:dispatch", "train:sync",
               "train:ckpt"}
COMPILE_SPANS = {"build:schedule", "place:kv", "compile:schedule"}


@pytest.fixture(autouse=True)
def _disabled_tracer():
    obs.disable()
    yield
    obs.disable()


@pytest.fixture(scope="module")
def llama():
    cfg = configs.get_smoke_config("llama3-8b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _engine(llama):
    cfg, _, params = llama
    return ServeEngine(cfg, params, batch=2, max_len=32, paged=True,
                       kv_block_size=4, prefill="batch", backend="pim")


def _serve(eng, lengths=(6, 7, 11)):
    rng = np.random.default_rng(0)
    for i, n in enumerate(lengths):
        eng.submit(Request(rid=i, max_tokens=3, prompt=rng.integers(
            0, eng.cfg.vocab_size, n, dtype=np.int32)))
    return eng.run()


def _trainer(tmp_path, steps=3, ckpt_every=1):
    from repro.data import DigitsDataset
    from repro.optim import make_optimizer
    from repro.train import Trainer, TrainerConfig

    opt = make_optimizer("adamw", lr=2e-3)
    ds = DigitsDataset(batch_size=8, seed=0)

    def init_state():
        p = lenet.init_lenet(jax.random.PRNGKey(0), LENET_CONFIG)
        return p, opt.init(p)

    def train_step(params, opt_state, batch):
        imgs, labels = batch
        loss, grads = jax.value_and_grad(lenet.lenet_loss)(
            params, jnp.asarray(imgs), jnp.asarray(labels))
        params, opt_state = opt.update(grads, opt_state, params)
        return params, opt_state, loss

    tc = TrainerConfig(total_steps=steps, ckpt_every=ckpt_every,
                       ckpt_dir=str(tmp_path / "ckpt"), async_ckpt=False)
    return Trainer(tc, train_step=train_step, init_state=init_state,
                   batch_fn=ds.batch, backend="pim")


def _host_spans(log_dir, names) -> list[tuple[str, str, int, int, dict]]:
    """(line, name, start, end, args) of every host event named in
    ``names`` in the profile under ``log_dir``."""
    path, = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in names:
                    s = int(e.start_ns)
                    out.append((line.name, e.name, s,
                                s + int(e.duration_ns), dict(e.stats)))
    return out


def _inside(child, parents) -> bool:
    return any(p[0] == child[0] and p[2] <= child[2] and child[3] <= p[3]
               for p in parents)


def test_serve_spans_reach_the_profile_with_args_and_nesting(tmp_path,
                                                             llama):
    with jax.profiler.trace(str(tmp_path)):
        eng = _engine(llama)
        assert len(_serve(eng)) == 3
    spans = _host_spans(str(tmp_path), SERVE_SPANS | COMPILE_SPANS)
    by_name = {}
    for sp in spans:
        by_name.setdefault(sp[1], []).append(sp)
    assert SERVE_SPANS | COMPILE_SPANS <= set(by_name)
    assert len(by_name["admit"]) == 3
    assert sorted(sp[4]["rid"] for sp in by_name["admit"]) == [0, 1, 2]
    for sp in by_name["prefill:batch"]:
        assert {"rid", "slot", "tokens", "bucket"} <= set(sp[4])
        assert sp[4]["bucket"] % 4 == 0 and sp[4]["bucket"] >= \
            sp[4]["tokens"]
    for sp in by_name["decode:tick"]:
        assert {"tick", "active", "keys"} <= set(sp[4])
        assert sp[4]["keys"] >= sp[4]["active"] >= 1
        # every lane streams at least one page; the pages cover the keys
        # and never exceed the block table's 2 lanes x 8 blocks
        assert sp[4]["grid_pages"] == 16
        assert 2 <= sp[4]["pages"] <= 16
        assert 4 * sp[4]["pages"] >= sp[4]["keys"]
    assert all("tick" in sp[4] for sp in by_name["tick"])
    ticks = by_name["tick"]
    for name in ("prefill:batch", "decode:tick", "sample:sync", "admit"):
        assert all(_inside(sp, ticks) for sp in by_name[name]), name
    # the sampled ids are read inside the decode span that needs them
    assert all(_inside(sp, by_name["decode:tick"])
               for sp in by_name["sample:sync"])


def test_train_spans_reach_the_profile_with_nesting(tmp_path):
    with jax.profiler.trace(str(tmp_path / "prof")):
        res = _trainer(tmp_path).run()
    assert len(res["losses"]) == 3
    spans = _host_spans(str(tmp_path / "prof"), TRAIN_SPANS | COMPILE_SPANS)
    by_name = {}
    for sp in spans:
        by_name.setdefault(sp[1], []).append(sp)
    assert TRAIN_SPANS | {"build:schedule", "compile:schedule"} \
        <= set(by_name)
    steps = by_name["train:step"]
    assert sorted(sp[4]["step"] for sp in steps) == [0, 1, 2]
    for name in TRAIN_SPANS - {"train:step"}:
        assert all(_inside(sp, steps) for sp in by_name[name]), name
    assert len(by_name["train:sync"]) == 3


class _Spy:
    def __init__(self):
        self.annotations = 0
        self.syncs = 0


@pytest.fixture
def spy(monkeypatch):
    s = _Spy()
    real_ann, real_sync = obs_trace.TraceAnnotation, jax.block_until_ready

    class CountingAnnotation(real_ann):
        def __init__(self, *a, **kw):
            s.annotations += 1
            super().__init__(*a, **kw)

    def counting_sync(x):
        s.syncs += 1
        return real_sync(x)

    monkeypatch.setattr(obs_trace, "TraceAnnotation", CountingAnnotation)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", CountingAnnotation)
    monkeypatch.setattr(jax, "block_until_ready", counting_sync)
    return s


def test_off_means_no_annotation_and_no_sync(tmp_path, llama, spy):
    assert not obs.recording()
    eng = _engine(llama)
    assert len(_serve(eng)) == 3
    _trainer(tmp_path).run()
    assert spy.annotations == 0
    assert spy.syncs == 0


def test_profiler_alone_annotates_but_never_syncs(tmp_path, llama, spy):
    eng = _engine(llama)
    _serve(eng, lengths=(6,))                   # warm: compile outside
    with jax.profiler.trace(str(tmp_path / "prof")):
        assert obs.recording() and not obs.is_enabled()
        _serve(eng, lengths=(7, 11))
        _trainer(tmp_path).run()
    assert spy.annotations > 0
    assert spy.syncs == 0


def test_lazy_span_args_are_built_only_when_recording(tmp_path):
    built = []

    def args():
        built.append(1)
        return {"n": 1}

    with obs.span("x", lane="t", lazy=args):
        pass
    assert built == []
    with obs.scoped() as tr:
        with obs.span("x", lane="t", lazy=args):
            pass
    assert built == [1] and tr.spans(name="x")[0].args == {"n": 1}
    with jax.profiler.trace(str(tmp_path)):
        with obs.span("x", lane="t", lazy=args):
            pass
    assert built == [1, 1]


@pytest.mark.parametrize("with_tracer", [False, True])
def test_lowered_programs_do_not_depend_on_the_profiler(tmp_path, llama,
                                                        with_tracer):
    eng = _engine(llama)
    _serve(eng, lengths=(6,))
    dec_args = (eng.params, eng.cache, jnp.zeros(eng.batch, jnp.int32),
                eng.kv.device_table(), jnp.asarray(eng._pos))
    pre_args = (eng.params, eng.cache, jnp.zeros(8, jnp.int32),
                eng.kv.device_table()[0], jnp.int32(0), jnp.int32(5))

    def lowered():
        jax.clear_caches()          # trace afresh, under what is on now
        return (eng.pim_program.jitted.lower(*dec_args).as_text(),
                eng._prefill_fn.lower(*pre_args).as_text())

    off = lowered()
    with jax.profiler.trace(str(tmp_path)):
        if with_tracer:
            with obs.scoped():
                on = lowered()
        else:
            on = lowered()
    assert all(off)
    assert on == off
