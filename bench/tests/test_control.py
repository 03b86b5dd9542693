"""What decides ``correct`` has to be able to say false. At smoke sizes on
the CPU: a run with the timed path broken underneath comes out not
correct, once for each fault a cell can have, and the control (the
reference in a lower precision, in the program's place) reads above the
limit where the program reads below it."""

import json
import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

import smoke_tree  # noqa: E402
from test_rehearsal import run_cell  # noqa: E402

TOKEN_ALTERED = """
import numpy as np, jax.numpy as jnp
import repro.serve.engine as E
_init = E.ServeEngine.__init__
def init(self, *a, **kw):
    _init(self, *a, **kw)
    def sample(logits):            # slot 0's token altered as it is made
        t = np.array(jnp.argmax(logits, -1))
        t[0] = (t[0] + 1) % logits.shape[-1]
        return t
    self.sample = sample
E.ServeEngine.__init__ = init
"""

_TRAIN_FAULT = """
import repro.train.trainer as T
_init = T.Trainer.__init__
def init(self, *a, **kw):
    _init(self, *a, **kw)
    real = self._step_fn
    def step(p, s, batch):
{body}
    self._step_fn = step
T.Trainer.__init__ = init
"""
STATE_UNCHANGED = _TRAIN_FAULT.format(
    body="        return p, s, real(p, s, batch)[2]")
# half of the batch left out: the first half stands in for the second,
# so the mean is taken over the first half alone
HALF_BATCH = _TRAIN_FAULT.format(body="""\
        import numpy as np
        x, y = batch
        n = len(x) // 2
        x = np.concatenate([x[:n], x[:n]]); y = np.concatenate([y[:n], y[:n]])
        return real(p, s, (x, y))""")


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return smoke_tree.build(tmp_path_factory.mktemp("checkout"))


@pytest.mark.parametrize("workload,fault", [
    ("smoke.smoke-chat", TOKEN_ALTERED),
    ("smoke.smoke-long", TOKEN_ALTERED),
    ("smoke.smoke-train", STATE_UNCHANGED),
    ("smoke.smoke-train", HALF_BATCH)],
    ids=["chat-token", "long-token", "train-unchanged", "train-half-batch"])
def test_a_broken_timed_path_is_not_correct(tree, workload, fault):
    line, err = run_cell(tree, workload, 0, prelude=fault)
    assert line["correct"] is False, line["checks"]
    assert any(s.endswith("FAIL") for s in err.splitlines())


def _calibrate(tree, workload, seed):
    code = (f"import sys\nsys.path.insert(0, {str(tree / 'bench')!r})\n"
            f"sys.path.insert(0, {str(tree / 'src')!r})\n"
            "import json, harness, calibrate\n"
            f"cell = harness.load_cell({workload!r})\n"
            "c = harness.CompileCounter()\n"
            "f = (calibrate.serve_seed(cell, %d, 2.0, c)\n"
            "     if cell.config['driver'] == 'serve'\n"
            "     else calibrate.train_seed(cell, %d, c))\n"
            "print(json.dumps(f))" % (seed, seed))
    import os
    import subprocess
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    p = subprocess.run([sys.executable, "-c", code], cwd=tree, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("seed", [3, 2 ** 33 + 5])
def test_serving_control_fails_where_the_program_passes(tree, seed):
    r = _calibrate(tree, "smoke.smoke-chat", seed)
    limit = smoke_tree.CHAT["check"]["max_logit_gap"]
    assert r["program_max_logit_gap"] <= limit < r["control_max_logit_gap"]


@pytest.mark.parametrize("seed", [3, 2 ** 33 + 5])
def test_training_control_fails_where_the_program_passes(tree, seed):
    r = _calibrate(tree, "smoke.smoke-train", seed)
    lim = smoke_tree.TRAIN["check"]
    names = list(lim)
    assert all(r[f"program_{n}"] <= lim[n] for n in names)
    assert any(r[f"control_{n}"] > lim[n] for n in names)
    assert any(r[f"half_batch_{n}"] > lim[n] for n in names)
