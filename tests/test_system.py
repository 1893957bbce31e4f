"""End-to-end behaviour tests for the full system.

* M-AVG trains a real (reduced) transformer on learnable bigram data and
  the loss drops; M-AVG reaches a lower loss than K-AVG at equal samples
  (the paper's headline claim, Figures 1-6).
* The jitted meta-step runs unchanged under a real multi-device mesh with
  the learner axis sharded (subprocess with 8 host devices) and produces
  the same losses as the single-device run — the SPMD-correctness
  integration test of the sharded meta step.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

pytestmark = pytest.mark.slow  # end-to-end training + subprocess mesh, ~90s

from repro.configs import get_config
from repro.configs.base import MAvgConfig
from repro.core.meta import init_state, make_meta_step
from repro.data import lm_batch_fn
from repro.models import api as model_api

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _train(algo, mu, steps=20, seed=0):
    cfg = get_config("qwen3-1.7b").reduced()
    mcfg = MAvgConfig(algorithm=algo, num_learners=4, k_steps=2,
                      learner_lr=0.5, momentum=mu)
    params = model_api.init_params(jax.random.PRNGKey(seed), cfg)
    state = init_state(params, mcfg)
    step = jax.jit(make_meta_step(
        lambda p, b: model_api.loss_fn(p, cfg, b), mcfg))
    bf = lm_batch_fn(cfg, 4, 2, 8, 32)
    losses = []
    for i in range(steps):
        b = bf(jax.random.fold_in(jax.random.PRNGKey(123), i), i)
        state, m = step(state, b)
        losses.append(float(m["loss"]))
    return losses


def test_mavg_trains_transformer():
    losses = _train("mavg", 0.6)
    assert losses[-1] < losses[0] - 0.5, losses


def test_mavg_beats_kavg_same_samples():
    """The paper's core claim at system level (same data, same steps)."""
    m = _train("mavg", 0.6, steps=25)
    k = _train("kavg", 0.0, steps=25)
    # compare average of last 5 losses (noise tolerance)
    m_tail = sum(m[-5:]) / 5
    k_tail = sum(k[-5:]) / 5
    assert m_tail < k_tail, (m_tail, k_tail)


_MESH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, sys
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_config
from repro.configs.base import MAvgConfig
from repro.core.meta import init_state, make_meta_step
from repro.data import lm_batch_fn
from repro.models import api as model_api
from repro.launch import specs as S

use_mesh = sys.argv[1] == "mesh"
use_pallas = len(sys.argv) > 2 and sys.argv[2] == "pallas"
cfg = get_config("qwen3-1.7b").reduced()
mcfg = MAvgConfig(algorithm="mavg", num_learners=4, k_steps=2,
                  learner_lr=0.5, momentum=0.6, use_pallas=use_pallas)
params = model_api.init_params(jax.random.PRNGKey(0), cfg)
state = init_state(params, mcfg)
loss_fn = lambda p, b: model_api.loss_fn(p, cfg, b)
step_fn = make_meta_step(loss_fn, mcfg)
if use_mesh:
    from repro.launch.mesh import make_debug_mesh
    mesh = make_debug_mesh(4, 2)
    # the mesh in context: the Pallas meta kernel runs under shard_map
    with jax.set_mesh(mesh):
        sh = S.state_shardings(cfg, mcfg, mesh)
        bsh = {k: NamedSharding(mesh, P("data")) for k in ("tokens", "labels")}
        step = jax.jit(step_fn, in_shardings=(sh, bsh), out_shardings=(sh, None))
        bf = lm_batch_fn(cfg, 4, 2, 8, 32)
        losses = []
        for i in range(4):
            b = bf(jax.random.fold_in(jax.random.PRNGKey(123), i), i)
            state, m = step(state, jax.device_put(b, bsh))
            losses.append(float(m["loss"]))
else:
    step = jax.jit(step_fn)
    bf = lm_batch_fn(cfg, 4, 2, 8, 32)
    losses = []
    for i in range(4):
        b = bf(jax.random.fold_in(jax.random.PRNGKey(123), i), i)
        state, m = step(state, b)
        losses.append(float(m["loss"]))
print(json.dumps(losses))
"""


def _run_mesh_script(tmp_path, *argv):
    script = tmp_path / "mesh_run.py"
    script.write_text(_MESH_SCRIPT)
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run(
        [sys.executable, str(script), *argv], env=env, capture_output=True,
        text=True, timeout=1200,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_meta_step_under_real_mesh(tmp_path):
    """Same program, 8 sharded host devices vs 1: losses must agree."""
    losses_mesh = _run_mesh_script(tmp_path, "mesh")
    losses_single = _run_mesh_script(tmp_path, "single")
    for a, b in zip(losses_mesh, losses_single):
        assert abs(a - b) < 5e-2, (losses_mesh, losses_single)


def test_meta_kernel_under_real_mesh(tmp_path):
    """The Pallas meta kernel under the mesh (shard_map, interpret mode
    here) against the jnp meta update on one device: losses must agree."""
    losses_mesh = _run_mesh_script(tmp_path, "mesh", "pallas")
    losses_single = _run_mesh_script(tmp_path, "single")
    for a, b in zip(losses_mesh, losses_single):
        assert abs(a - b) < 5e-2, (losses_mesh, losses_single)
