"""End-to-end behaviour: LeNet training convergence + fault-tolerant
resume reproduces the uninterrupted run exactly; MoE routing correctness;
multi-device sharding equivalence (subprocess)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.lenet5 import CONFIG as LENET
from repro.data import DigitsDataset
from repro.models import lenet
from repro.optim import make_optimizer
from repro.train import Trainer, TrainerConfig

from helpers import run_with_devices


def _lenet_setup(ckpt_dir, total, fail_at=None):
    opt = make_optimizer("adamw", lr=2e-3)
    ds = DigitsDataset(batch_size=32, seed=0)

    def init_state():
        p = lenet.init_lenet(jax.random.PRNGKey(0), LENET)
        return p, opt.init(p)

    def train_step(params, opt_state, batch):
        imgs, labels = batch
        loss, grads = jax.value_and_grad(lenet.lenet_loss)(
            params, jnp.asarray(imgs), jnp.asarray(labels))
        params, opt_state = opt.update(grads, opt_state, params)
        return params, opt_state, loss

    tc = TrainerConfig(total_steps=total, ckpt_every=8,
                       ckpt_dir=str(ckpt_dir), async_ckpt=False,
                       fail_at_step=fail_at)
    return Trainer(tc, train_step=train_step, init_state=init_state,
                   batch_fn=ds.batch)


def test_lenet_learns(tmp_path):
    tr = _lenet_setup(tmp_path / "a", total=150)
    res = tr.run()
    # single-batch losses are noisy (the seed run sat right at the old
    # <1.6 cliff at step 79 and bounced above it at 99); average the tail
    tail = float(np.mean(res["losses"][-10:]))
    assert res["losses"][0] > tail
    assert tail < 1.5


def test_resume_reproduces_uninterrupted_run(tmp_path):
    """Train 30 steps straight vs crash-at-20 + resume: identical losses
    after the restart point (stateless data pipeline + exact checkpoint)."""
    straight = _lenet_setup(tmp_path / "s", total=30).run()

    crashed = _lenet_setup(tmp_path / "c", total=30, fail_at=20)
    with pytest.raises(RuntimeError, match="injected"):
        crashed.run()
    resumed = _lenet_setup(tmp_path / "c", total=30).run()
    assert resumed["resumed"]
    # losses from the resumed start must match the straight run's tail
    start = resumed["start_step"]
    np.testing.assert_allclose(resumed["losses"],
                               straight["losses"][start:], rtol=1e-5)


def test_moe_equals_dense_when_topk_is_all(rng):
    """With top_k = n_experts and ample capacity, MoE == softmax-weighted
    sum of every expert (routing/dispatch correctness oracle)."""
    import dataclasses
    from repro import configs
    from repro.models import moe
    cfg = dataclasses.replace(
        configs.get_smoke_config("granite-moe-1b-a400m"),
        n_experts=4, top_k=4, capacity_factor=8.0)
    params = moe.init_moe(jax.random.PRNGKey(0), cfg.d_model, 4,
                          cfg.moe_d_ff, jnp.float32)
    x = jnp.asarray(rng.standard_normal((2, 8, cfg.d_model)) * 0.3,
                    jnp.float32)
    got = moe.moe_block(x, params, cfg)

    xf = x.reshape(-1, cfg.d_model)
    probs = jax.nn.softmax((xf @ params["router"]).astype(jnp.float32), -1)
    want = jnp.zeros_like(xf)
    for ei in range(4):
        g = jax.nn.silu(xf @ params["w_gate"][ei]) * (xf @ params["w_up"][ei])
        out_e = g @ params["w_down"][ei]
        want = want + probs[:, ei:ei + 1] * out_e
    np.testing.assert_allclose(np.asarray(got.reshape(-1, cfg.d_model)),
                               np.asarray(want), atol=2e-4, rtol=1e-2)


def test_moe_respects_capacity(rng):
    """Tokens over capacity are dropped (zero contribution), not misrouted."""
    import dataclasses
    from repro import configs
    from repro.models import moe
    cfg = dataclasses.replace(
        configs.get_smoke_config("granite-moe-1b-a400m"),
        n_experts=2, top_k=1, capacity_factor=0.1)
    params = moe.init_moe(jax.random.PRNGKey(0), cfg.d_model, 2,
                          cfg.moe_d_ff, jnp.float32)
    x = jnp.asarray(rng.standard_normal((1, 64, cfg.d_model)), jnp.float32)
    out = moe.moe_block(x, params, cfg)
    grp = moe._n_groups(cfg, 64)
    cap = moe.capacity(64 // grp, 2, 1, 0.1)
    nz = np.abs(np.asarray(out[0])).sum(-1) > 1e-6
    assert nz.sum() <= grp * cap * 2


# -- multi-device equivalence (subprocess: forces 8 host devices) -------------

_SHARDED_EQUIV = r"""
import jax, jax.numpy as jnp, numpy as np, dataclasses
from jax.sharding import NamedSharding, PartitionSpec as P
from repro import configs
from repro.launch import steps as steps_mod
from repro.launch.mesh import auto_mesh
from repro.parallel import sharding
from repro.optim import make_optimizer

assert len(jax.devices()) == 8, jax.devices()
cfg = configs.get_smoke_config("llama3-8b")
mesh = auto_mesh((4, 2), ("data", "model"))
rules = sharding.single_pod_rules(mesh)

from repro.models.transformer import build_model
model = build_model(cfg)
params = model.init(jax.random.PRNGKey(0))
opt = make_optimizer("adamw", lr=1e-3)
opt_state = opt.init(params)
batch = {
    "tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                                 cfg.vocab_size),
    "labels": jax.random.randint(jax.random.PRNGKey(2), (4, 16), 0,
                                 cfg.vocab_size),
}
step = steps_mod.make_train_step(cfg, optimizer_name="adamw", lr=1e-3)

# single-device reference
p1, o1, loss1 = jax.jit(step)(params, opt_state, batch)

# sharded
p_specs = sharding.param_specs(params, rules)
ns = lambda t: jax.tree.map(lambda s: NamedSharding(mesh, s), t,
                            is_leaf=lambda x: isinstance(x, P))
with mesh, sharding.use_rules(rules):
    sh_params = jax.device_put(params, ns(p_specs))
    sh_batch = jax.device_put(batch, NamedSharding(mesh, P("data", None)))
    p2, o2, loss2 = jax.jit(step)(sh_params, opt_state, sh_batch)

assert abs(float(loss1) - float(loss2)) < 2e-4, (float(loss1), float(loss2))
d = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a.astype(jnp.float32)
                 - b.astype(jnp.float32)))), p1, p2)
mx = max(jax.tree.leaves(d))
assert mx < 2e-3, mx
print("SHARDED_EQUIV_OK", float(loss1), float(loss2), mx)
"""


def test_sharded_train_step_matches_single_device():
    res = run_with_devices(_SHARDED_EQUIV, n_devices=8, timeout=500)
    assert "SHARDED_EQUIV_OK" in res.stdout, res.stdout + res.stderr


_COMPRESSED_PSUM = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import auto_mesh
from repro.optim import compressed_psum

mesh = auto_mesh((8,), ("data",))
g_global = jax.random.normal(jax.random.PRNGKey(0), (8, 128))

def f(g):
    red, err = compressed_psum({"g": g[0]}, "data", None)
    return red["g"][None], err["g"][None]

red, err = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P("data"),
                                 out_specs=(P("data"), P("data"))))(g_global)
want = jnp.mean(g_global, axis=0)
got = red[0]
rel = float(jnp.abs(got - want).max() / jnp.abs(want).max())
assert rel < 0.02, rel      # int8 quantization error bound
print("COMPRESSED_PSUM_OK", rel)
"""


def test_compressed_psum_multidevice():
    """Runs in-process when the session already has >= 8 devices (CI
    exports ``XLA_FLAGS=--xla_force_host_platform_device_count=8``);
    otherwise forces them in a subprocess — never skipped either way."""
    import jax

    if len(jax.devices()) >= 8:
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        from repro.launch.mesh import auto_mesh
        from repro.optim import compressed_psum

        mesh = auto_mesh((8,), ("data",))
        g_global = jax.random.normal(jax.random.PRNGKey(0), (8, 128))

        def f(g):
            red, err = compressed_psum({"g": g[0]}, "data", None)
            return red["g"][None], err["g"][None]

        red, err = jax.jit(jax.shard_map(
            f, mesh=mesh, in_specs=P("data"),
            out_specs=(P("data"), P("data"))))(g_global)
        want = jnp.mean(g_global, axis=0)
        rel = float(jnp.abs(red[0] - want).max() / jnp.abs(want).max())
        assert rel < 0.02, rel      # int8 quantization error bound
        return
    res = run_with_devices(_COMPRESSED_PSUM, n_devices=8, timeout=300)
    assert "COMPRESSED_PSUM_OK" in res.stdout, res.stdout + res.stderr
