"""Elastic scaling: a checkpoint written on one mesh resumes on another
(here 1 device -> 4-device data-parallel mesh) with loss continuity —
checkpoints are host numpy (mesh-agnostic) and the data pipeline is a pure
function of the step, so rescale is exact up to reduction order."""

from helpers import run_with_devices

_PHASE1 = r"""
import jax, jax.numpy as jnp
from repro import configs
from repro.data import TokenStream
from repro.launch import steps as steps_mod
from repro.models.transformer import build_model
from repro.optim import make_optimizer
from repro.train import Trainer, TrainerConfig

cfg = configs.get_smoke_config("llama3-8b")
model = build_model(cfg)
opt = make_optimizer("adamw", lr=1e-3)
ts = TokenStream(vocab_size=cfg.vocab_size, seq_len=32, batch_size=8, seed=3)
step = steps_mod.make_train_step(cfg, lr=1e-3)
tr = Trainer(TrainerConfig(total_steps=11, ckpt_every=5,
                           ckpt_dir=CKPT_DIR, async_ckpt=False),
             train_step=step, init_state=lambda: (
                 model.init(jax.random.PRNGKey(0)),
                 opt.init(model.init(jax.random.PRNGKey(0)))),
             batch_fn=ts.batch)
res = tr.run()
print("PHASE1_OK", res["losses"][-1])
"""

_PHASE2 = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro import configs
from repro.data import TokenStream
from repro.launch import steps as steps_mod
from repro.launch.mesh import auto_mesh
from repro.models.transformer import build_model
from repro.optim import make_optimizer
from repro.parallel import sharding
from repro.train import Trainer, TrainerConfig

assert len(jax.devices()) == 4
cfg = configs.get_smoke_config("llama3-8b")
mesh = auto_mesh((4, 1), ("data", "model"))
rules = sharding.single_pod_rules(mesh)
model = build_model(cfg)
opt = make_optimizer("adamw", lr=1e-3)
ts = TokenStream(vocab_size=cfg.vocab_size, seq_len=32, batch_size=8, seed=3)
step = steps_mod.make_train_step(cfg, lr=1e-3)

def init_state():
    params = model.init(jax.random.PRNGKey(0))
    specs = sharding.param_specs(params, rules)
    params = jax.device_put(params, jax.tree.map(
        lambda sp: NamedSharding(mesh, sp), specs,
        is_leaf=lambda x: isinstance(x, P)))
    return params, opt.init(params)

with mesh, sharding.use_rules(rules):
    tr = Trainer(TrainerConfig(total_steps=16, ckpt_every=5,
                               ckpt_dir=CKPT_DIR,
                               async_ckpt=False),
                 train_step=step, init_state=init_state, batch_fn=ts.batch)
    assert tr.resumed and tr.start_step == 11, (tr.resumed, tr.start_step)
    res = tr.run()
losses = res["losses"]
assert all(np.isfinite(losses)), losses
print("PHASE2_OK", tr.start_step, losses[0], losses[-1])
"""


def test_elastic_rescale_1_to_4_devices(tmp_path):
    ckpt = repr(str(tmp_path / "ckpt"))
    r1 = run_with_devices(_PHASE1.replace("CKPT_DIR", ckpt), n_devices=1,
                          timeout=400)
    assert "PHASE1_OK" in r1.stdout, r1.stdout + r1.stderr
    l1 = float(r1.stdout.split("PHASE1_OK")[1].split()[0])
    r2 = run_with_devices(_PHASE2.replace("CKPT_DIR", ckpt), n_devices=4,
                          timeout=400)
    assert "PHASE2_OK" in r2.stdout, r2.stdout + r2.stderr
    parts = r2.stdout.split("PHASE2_OK")[1].split()
    first_resumed_loss = float(parts[1])
    # loss continuity across the rescale (same data, restored params)
    assert abs(first_resumed_loss - l1) < 0.5 * max(l1, 1.0)
