"""Sharding-rule unit tests (uses AbstractMesh — no devices needed)."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import get_config
from repro.launch import specs as S
from repro.models import api as model_api
from repro.sharding import add_learner_axis, make_param_specs

MESH = AbstractMesh((16, 16), ("data", "model"))


def _specs(arch, **kw):
    cfg = get_config(arch)
    params = S.abstract_params(cfg)
    return params, make_param_specs(params, MESH, **kw)


def test_llama_attention_head_parallel():
    params, specs = _specs("llama3-405b")
    # wq (126, d, h, hd): heads divisible by 16 -> head-parallel
    assert specs["blocks"]["attn"]["wq"] == P(None, None, "model", None)
    assert specs["blocks"]["attn"]["wo"] == P(None, "model", None, None)
    # mlp wi (126, d, 2, ff): ff-parallel
    assert specs["blocks"]["mlp"]["wi"] == P(None, None, None, "model")
    assert specs["blocks"]["mlp"]["wo"] == P(None, "model", None)
    assert specs["embed"]["embedding"] == P("model", None)


def test_qwen2_head_fallback():
    """28 heads don't divide 16 -> fall back to d_model row-parallel."""
    params, specs = _specs("qwen2-7b")
    assert specs["blocks"]["attn"]["wq"] == P(None, "model", None, None)
    # wo (h, hd, d): heads 28 not divisible -> output dim
    assert specs["blocks"]["attn"]["wo"] == P(None, None, None, "model")


def test_moe_expert_parallel():
    params, specs = _specs("kimi-k2-1t-a32b")
    assert specs["blocks"]["moe"]["w_in"] == P(None, "model", None, None, None)
    assert specs["blocks"]["moe"]["w_out"] == P(None, "model", None, None)
    assert specs["blocks"]["moe"]["router"] == P(None, None, "model")


def test_norms_replicated():
    params, specs = _specs("qwen3-1.7b")
    assert specs["final_norm"]["scale"] == P(None)
    assert specs["blocks"]["attn_norm"]["scale"] == P(None, None)


def test_fsdp_second_axis():
    params, specs = _specs("llama3-405b", fsdp_axis="data")
    # wq gets model on heads + data on d_model
    assert specs["blocks"]["attn"]["wq"] == P(None, "data", "model", None)


def test_learner_axis_prepend():
    params, specs = _specs("qwen3-1.7b")
    lspecs = add_learner_axis(specs, "data")
    assert lspecs["blocks"]["attn"]["wq"] == P("data", None, None, "model", None)


def test_every_leaf_has_spec_every_arch():
    """No parameter silently missing a rule (catches new layer types)."""
    from repro.configs import ARCH_IDS

    for arch in ARCH_IDS:
        params, specs = _specs(arch)
        np_, ns_ = len(jax.tree.leaves(params)), len(
            jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
        )
        assert np_ == ns_, arch


def test_divisibility_every_arch():
    """Sharded dims always divisible by the mesh-axis size."""
    from repro.configs import ARCH_IDS

    for arch in ARCH_IDS:
        params, specs = _specs(arch, fsdp_axis="data")
        flat_p = jax.tree_util.tree_leaves_with_path(params)
        flat_s = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
        for (path, leaf), spec in zip(flat_p, flat_s):
            for dim, ax in enumerate(spec):
                if ax is None:
                    continue
                size = MESH.shape[ax] if isinstance(ax, str) else 16
                assert leaf.shape[dim] % size == 0, (arch, path, spec)
