"""The manual shard_map expert-parallel path must produce the same
numbers as the GSPMD gather/scatter path (serving correctness).

Runs in a subprocess with 8 forced host devices (the main test process
must keep seeing 1 CPU device)."""
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses, json
import jax, jax.numpy as jnp
import numpy as np
from repro.configs import get_config
from repro.models import moe

cfg = dataclasses.replace(get_config("deepseek-moe-16b").reduced(),
                          dtype="float32")
p = moe.init_moe(jax.random.PRNGKey(0), cfg)
x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model)) * 0.5
out_ref, aux_ref = moe.moe_layer(x, p, cfg)
from repro.launch.mesh import make_debug_mesh
mesh = make_debug_mesh(2, 4)
moe.set_expert_axis("model", mesh)
with mesh:
    out_sm, aux_sm = jax.jit(lambda x, p: moe.moe_layer(x, p, cfg))(x, p)
moe.set_expert_axis(None, None)
np.testing.assert_allclose(np.asarray(out_sm), np.asarray(out_ref),
                           rtol=2e-4, atol=2e-4)
assert abs(float(aux_sm) - float(aux_ref)) < 1e-6
print(json.dumps({"ok": True}))
"""


def test_shard_map_moe_matches_gspmd(tmp_path):
    script = tmp_path / "sm_moe.py"
    script.write_text(SCRIPT)
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run(
        [sys.executable, str(script)], env=env, capture_output=True,
        text=True, timeout=900,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    assert json.loads(out.stdout.strip().splitlines()[-1])["ok"]
