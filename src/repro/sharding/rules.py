"""Sharding rules: parameter-name → PartitionSpec.

Rules are expressed as *negative-dim preference lists* so they apply
unchanged to layer-stacked parameters (scan adds leading axes which stay
unsharded). For each leaf we place the tensor-parallel (``model``) axis on
the first preferred dim whose size divides the axis; optionally an FSDP
axis (``data`` inside a learner, hierarchical mode / serving of the
largest configs) on a second dim.

Examples
--------
* ``wq (d_model, heads, head_dim)`` prefers heads (Megatron head-parallel);
  qwen2-7b's 28 heads don't divide a 16-way model axis, so it falls back to
  d_model (row-parallel with a psum, GSPMD inserts it).
* MoE ``w_in (E, d, 2, d_e)`` shards the expert dim — expert parallelism.
* xLSTM/mamba projections shard the inner dim.
"""
from __future__ import annotations

import logging

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

log = logging.getLogger(__name__)

# name -> preference list of negative dims for the model (TP) axis.
# (A variant placing the second/fsdp axis on head_dim instead of d_model
# was tried and REFUTED — it doubled all-gather traffic on qwen1.5-110b
# prefill without touching the all-reduce term.)
PREFS: dict[str, tuple[int, ...]] = {
    # attention (d, h, hd) / (h, hd, d)
    "wq": (-2, -3),
    "wk": (-2, -3),
    "wv": (-2, -3),
    "wo": (-3, -1),
    "bq": (-2,),
    "bk": (-2,),
    "bv": (-2,),
    # mlp
    "wi": (-1, -3),
    # embeddings
    "embedding": (-2,),
    "head": (-1,),
    # moe
    "router": (-1,),
    "w_in": (-4,),
    "w_out": (-3,),
    # xlstm / mamba inner projections
    "w_up": (-1, -3),
    "w_down": (-2,),
    "w_xz": (-1, -3),
    "w_ssm_out": (-2,),
    "conv": (-1,),
    "w_bc": (-2,),
    "w_dt_down": (-2,),
    "w_dt_up": (-1,),
    "A_log": (-2,),
    "D": (-1,),
    "b_dt": (-1,),
    "w_i": (-2,),
    "w_f": (-2,),
    # sLSTM per-head recurrent + gates
    "r_i": (-1,),
    "r_f": (-1,),
    "r_z": (-1,),
    "r_o": (-1,),
    "w_z": (-1,),
    "w_o": (-1,),
    "b_i": (-1,),
    "b_f": (-1,),
    "b_z": (-1,),
    "b_o": (-1,),
}

# shared-mlp 'wo' (f, d) wants (-2,); attention 'wo' (h, hd, d) wants (-3, -1).
# Disambiguated by rank in _prefs_for.
REPLICATED = {"scale", "beta_attn", "beta_ssm", "meta", "patch_pos"}


def _prefs_for(name: str, ndim_base: int) -> tuple[int, ...]:
    if name == "wo" and ndim_base == 2:  # mlp down-proj (f, d)
        return (-2,)
    if name in ("w_i", "w_f") and ndim_base == 3:  # sLSTM gate (d, nh, hd)
        return (-1,)
    if name in ("b_i", "b_f") and ndim_base == 1:  # mLSTM gate bias (nh,)
        return ()
    return PREFS.get(name, ())


def _axis_size(mesh: Mesh, axis) -> int:
    if isinstance(axis, tuple):
        out = 1
        for a in axis:
            out *= mesh.shape[a]
        return out
    return mesh.shape[axis]


def leaf_spec(path, leaf, mesh: Mesh, *, model_axis="model", fsdp_axis=None,
              stack_dims: int = 0) -> P:
    """Compute the PartitionSpec for one parameter leaf.

    stack_dims: number of leading scan/stack dims (inferred by caller or 0);
    we simply never shard dims that a preference doesn't reach, so layer
    stacking needs no special handling (negative indexing).
    """
    name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
    ndim = leaf.ndim
    spec = [None] * ndim
    if name in REPLICATED or ndim == 0:
        return P(*spec)
    used = set()
    prefs = _prefs_for(name, ndim - stack_dims)
    if model_axis is not None:
        msize = _axis_size(mesh, model_axis)
        for neg in prefs:
            dim = ndim + neg
            if 0 <= dim < ndim and leaf.shape[dim] % msize == 0 and leaf.shape[dim] >= msize:
                spec[dim] = model_axis
                used.add(dim)
                break
    if fsdp_axis is not None:
        fsize = _axis_size(mesh, fsdp_axis)
        # FSDP axis goes on the first remaining preferred dim, else the
        # largest remaining divisible dim (skipping stacked leading dims).
        candidates = [ndim + n for n in prefs if (ndim + n) not in used]
        rest = [
            d
            for d in range(stack_dims, ndim)
            if d not in used and d not in candidates
        ]
        rest.sort(key=lambda d: -leaf.shape[d])
        for dim in candidates + rest:
            if 0 <= dim < ndim and leaf.shape[dim] % fsize == 0 and leaf.shape[dim] >= fsize:
                spec[dim] = fsdp_axis
                break
    return P(*spec)


def make_param_specs(params, mesh: Mesh, *, model_axis="model", fsdp_axis=None,
                     stack_dims_fn=None):
    """Pytree of PartitionSpec matching ``params``."""

    def f(path, leaf):
        sd = stack_dims_fn(path) if stack_dims_fn else _default_stack_dims(path)
        return leaf_spec(
            path, leaf, mesh, model_axis=model_axis, fsdp_axis=fsdp_axis,
            stack_dims=sd,
        )

    return jax.tree_util.tree_map_with_path(f, params)


def _default_stack_dims(path) -> int:
    keys = [p.key if hasattr(p, "key") else str(p) for p in path]
    for k in keys:
        if k == "mlstm":
            return 2  # (groups, blocks-per-group, ...)
        if k in ("blocks", "dense_blocks", "slstm"):
            return 1
    return 0


def add_learner_axis(specs, learner_axes):
    """Prepend the learner mesh axis to every spec (stacked learner copies)."""
    return jax.tree.map(
        lambda s: P(learner_axes, *s), specs,
        is_leaf=lambda x: isinstance(x, P),
    )


def named(specs, mesh: Mesh):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, P),
    )
