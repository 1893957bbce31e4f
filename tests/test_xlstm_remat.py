"""The xLSTM stack's recompute boundary (models/xlstm.py ``_scan_groups``).

  XR1  the gradient of ``loss_fn``, with each block its own checkpoint,
       matches the same block stack with no ``jax.checkpoint`` at all.
  XR2  the compiled ``jit(grad(loss_fn))`` stacks no block's residuals
       across the inner scan over a super-block's mLSTM blocks: the
       buffers written by ``dynamic-update-slice`` outside every ``obs.*``
       scope stay within 3x the parameter bytes (a checkpoint around the
       whole super-block writes about 7x).
"""
import dataclasses
import re
import statistics

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import xlstm

# 2 super-blocks of 3 mLSTM + 1 sLSTM; S divides by the chunk, so the
# chunkwise mLSTM runs
CFG = dataclasses.replace(get_config("xlstm-350m").reduced(), num_layers=8,
                          d_model=64, head_dim=16, slstm_every=4,
                          dtype="float32")
B, S = 2, 2 * xlstm.MLSTM_CHUNK
_DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "pred": 1}


@pytest.fixture(scope="module")
def setup():
    params = xlstm.init(jax.random.PRNGKey(0), CFG)
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                              CFG.vocab_size, jnp.int32)
    return params, {"tokens": toks, "labels": toks}


def _loss(params, batch):
    return xlstm.loss_fn(params, CFG, batch)[0]


def _checkpoints(params, batch):
    # a fresh function, so that no cached trace answers
    jaxpr = str(jax.make_jaxpr(lambda p, b: _loss(p, b))(params, batch))
    return len(re.findall(r"= (?:checkpoint|remat2?)\[", jaxpr))


def test_xr1_block_checkpoint_grad_matches_no_checkpoint(setup, monkeypatch):
    params, batch = setup
    assert _checkpoints(params, batch) == 2  # the two blocks' bodies
    got = jax.jit(jax.grad(_loss))(params, batch)
    monkeypatch.setattr(jax, "checkpoint", lambda fun, **kw: fun)
    assert _checkpoints(params, batch) == 0
    ref = jax.jit(jax.grad(lambda p, b: _loss(p, b)))(params, batch)
    ref_norm = {jax.tree_util.keystr(p): float(jnp.linalg.norm(r))
                for p, r in jax.tree_util.tree_leaves_with_path(ref)}
    median = statistics.median(ref_norm.values())
    for (path, g), r in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(ref)):
        name = jax.tree_util.keystr(path)
        # the input-gate biases' gradients are ~1e-9 at init: measure them
        # against the median leaf's scale
        scale = median if "'b_i'" in name else ref_norm[name]
        gap = float(jnp.linalg.norm(g - r)) / scale
        assert gap <= 1e-5, (name, gap)


def test_xr2_no_residual_stack_across_blocks(setup):
    params, batch = setup
    compiled = jax.jit(jax.grad(_loss)).lower(params, batch).compile()
    param_bytes = sum(a.size * a.dtype.itemsize
                      for a in jax.tree.leaves(params))
    written = 0
    for line in compiled.as_text().splitlines():
        if "dynamic-update-slice(" not in line:
            continue
        op_name = re.search(r'op_name="([^"]*)"', line)
        if op_name and "obs." in op_name.group(1):
            continue
        dtype, dims = re.search(r"=\s*(\w+)\[([\d,]*)\]", line).groups()
        written += (int(np.prod([int(d) for d in dims.split(",") if d]))
                    * _DTYPE_BYTES[dtype])
    assert written > 0
    assert written <= 3 * param_bytes, (written, param_bytes)
