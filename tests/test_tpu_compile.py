"""The meta-path Pallas kernels compile for a TPU v5e at the xlstm-350m
packed width, and the sLSTM time-loop kernels at the benchmark cell's
shapes, without a chip.

Each test lowers one kernel wrapper of ``repro.kernels.ops``, as the CLI
reaches it (same block choice), or one sLSTM block's value and gradient,
in compiled mode for one described v5e chip and asserts the Mosaic
kernel (``tpu_custom_call``) is in the compiled program. What interpret
mode cannot show — a slice off the tiling, too much VMEM, a block that
does not divide — fails here.

The v5e topology is described inside a module fixture, never at import:
only one process may load the TPU library, and under several test workers
only the worker given this file does.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

# xlstm-350m packed meta plane: 500.7M params -> (3911776, 128) f32
ROWS = 3_911_776
L = 2


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip cannot be read back from the
    # persistent cache without one; keep it out of the cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        jax.config.update("jax_enable_compilation_cache", prev)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(one_chip, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("ldtype", [jnp.float32, jnp.bfloat16])
def test_fused_momentum_broadcast(one_chip, ldtype):
    plane = _sds(one_chip, (ROWS, 128))
    txt = _compiled_text(
        lambda w, v, a: ops.fused_momentum_broadcast(
            w, v, a, mu=0.7, eta=1.0, num_learners=L, ldtype=ldtype,
            interpret=False),
        plane, plane, plane)
    assert "tpu_custom_call" in txt


def test_block_momentum(one_chip):
    plane = _sds(one_chip, (ROWS, 128))
    txt = _compiled_text(
        lambda w, v, a: ops.block_momentum(w, v, a, mu=0.7, interpret=False),
        plane, plane, plane)
    assert "tpu_custom_call" in txt


def test_pack_update(one_chip):
    stack = _sds(one_chip, (L, ROWS, 128))
    txt = _compiled_text(
        lambda w, g, e, u: ops.pack_update(w, g, e, u, interpret=False),
        _sds(one_chip, (L, ROWS, 128), jnp.bfloat16),
        _sds(one_chip, (ROWS, 128)), stack, stack)
    assert "tpu_custom_call" in txt


def test_pack_compress(one_chip):
    stack = _sds(one_chip, (L, ROWS, 128))
    txt = _compiled_text(
        lambda d, u: ops.pack_compress(d, u, interpret=False), stack, stack)
    assert "tpu_custom_call" in txt


def test_quantize(one_chip):
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    txt = _compiled_text(
        lambda x, k: ops.quantize(x, k, interpret=False)[:2],
        _sds(one_chip, (ROWS, 128)), key)
    assert "tpu_custom_call" in txt


def test_neighbor_mix(one_chip):
    txt = _compiled_text(
        lambda x, w: ops.neighbor_mix(x, w, interpret=False),
        _sds(one_chip, (L, ROWS, 128)), _sds(one_chip, (L, L)))
    assert "tpu_custom_call" in txt


def test_robust_reduce(one_chip):
    txt = _compiled_text(
        lambda x: ops.robust_reduce(x, trim=1, interpret=False),
        _sds(one_chip, (4, ROWS, 128)))
    assert "tpu_custom_call" in txt


def test_slstm_block_value_and_grad(one_chip, monkeypatch):
    """One xlstm-350m sLSTM block's value and gradient at the benchmark
    cell's shapes (B=4, S=512, bf16 learner weights and activations)
    holds the forward and backward kernels of its time loop."""
    from repro.configs import get_config
    from repro.models import xlstm

    # the model decides from the platform; compile it as on the chip
    monkeypatch.setattr(ops, "_platform", lambda: "tpu")
    cfg = get_config("xlstm-350m")
    bp = jax.tree.map(
        lambda a: _sds(one_chip, a.shape, jnp.bfloat16),
        jax.eval_shape(lambda: xlstm._init_slstm_block(
            jax.random.PRNGKey(0), cfg)))
    x = _sds(one_chip, (4, 512, cfg.d_model), jnp.bfloat16)
    txt = _compiled_text(jax.value_and_grad(
        lambda p, x: jnp.sum(xlstm.slstm_seq(p, cfg, x)[0].astype(
            jnp.float32))), bp, x)
    calls = [ln for ln in txt.splitlines()
             if "custom-call(" in ln and "tpu_custom_call" in ln]
    assert len(calls) == 2, calls
    assert any("slstm_fwd" in ln for ln in calls)
    assert any("slstm_bwd" in ln for ln in calls)
