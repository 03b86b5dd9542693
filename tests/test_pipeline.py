"""GPipe pipeline over the pod axis: pipelined == unpipelined reference."""

from helpers import run_with_devices

_PIPE = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import auto_mesh
from repro.parallel.pipeline import make_pipelined_fn

P_STAGES, LAYERS_PER_STAGE, N_MICRO, MB, D = 2, 3, 4, 2, 16
mesh = auto_mesh((P_STAGES,), ("pod",))

key = jax.random.PRNGKey(0)
w = jax.random.normal(key, (P_STAGES, LAYERS_PER_STAGE, D, D)) * 0.3
x = jax.random.normal(jax.random.PRNGKey(1), (N_MICRO, MB, D))

def stage_fn(stage_w, xm):
    def layer(c, wl):
        return jnp.tanh(c @ wl), None
    y, _ = jax.lax.scan(layer, xm, stage_w)
    return y

# unpipelined reference: all stages sequentially on each microbatch
ref = x
for s in range(P_STAGES):
    ref = jax.vmap(lambda xm: stage_fn(w[s], xm))(ref)

piped = jax.jit(make_pipelined_fn(stage_fn, mesh, axis="pod",
                                  n_micro=N_MICRO))(x, w)
err = float(jnp.abs(piped - ref).max())
assert err < 1e-5, err
print("PIPELINE_OK", err)
"""


def test_gpipe_matches_reference():
    res = run_with_devices(_PIPE, n_devices=2, timeout=300)
    assert "PIPELINE_OK" in res.stdout, res.stdout + res.stderr
