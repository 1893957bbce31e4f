"""Abstract input specs (ShapeDtypeStruct) + sharding assembly for the
launchers and for compiles against a described chip.

input_specs() provides weak-type-correct, shardable stand-ins for every
model input — no device allocation — including the stub modality
frontends (audio frame embeddings, VLM patch embeddings) per the
assignment carve-out.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import (
    InputShape,
    MAvgConfig,
    ModelConfig,
)
from repro.core.meta import MetaState, init_state
from repro.launch import mesh as meshlib
from repro.models import api as model_api
from repro.sharding import add_learner_axis, make_param_specs

DRYRUN_K_STEPS = 2  # local steps per meta-step in the lowered train program
SERVE_FSDP_THRESHOLD = 20e9  # params above this get FSDP-sharded weights


def sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


# ---------------------------------------------------------------------------
# abstract params / state
# ---------------------------------------------------------------------------


def abstract_params(cfg: ModelConfig):
    return jax.eval_shape(
        lambda k: model_api.init_params(k, cfg), jax.random.PRNGKey(0)
    )


def abstract_state(cfg: ModelConfig, mcfg: MAvgConfig):
    params = abstract_params(cfg)
    return jax.eval_shape(lambda p: init_state(p, mcfg), params)


# ---------------------------------------------------------------------------
# train inputs: (L, K, B_local, ...) per learner per local step
# ---------------------------------------------------------------------------


def train_input_specs(cfg: ModelConfig, shape: InputShape, num_learners: int,
                      k_steps: int = DRYRUN_K_STEPS) -> dict:
    assert shape.global_batch % num_learners == 0, (
        f"{shape.name}: global_batch {shape.global_batch} not divisible by "
        f"P={num_learners}"
    )
    b_loc = shape.global_batch // num_learners
    lead = (num_learners, k_steps, b_loc)
    out = {}
    for name, (shp, dtype) in model_api.batch_shapes(cfg, 1, shape.seq_len).items():
        out[name] = sds(lead + shp[1:], dtype)
    return out


def train_input_shardings(cfg: ModelConfig, mesh, learner_axes) -> dict:
    def spec(_name, s):
        return NamedSharding(mesh, P(learner_axes, *([None] * (len(s.shape) - 1))))

    shapes = model_api.batch_shapes(cfg, 1, 8)
    return {name: NamedSharding(mesh, P(learner_axes)) for name in shapes}


def _batch_axes(mesh, batch: int):
    """Largest prefix of (pod, data) axes that divides the batch."""
    axes = [a for a in ("pod", "data") if a in mesh.shape]
    chosen = []
    size = 1
    for a in axes:
        if batch % (size * mesh.shape[a]) == 0:
            chosen.append(a)
            size *= mesh.shape[a]
    if not chosen:
        return None
    return tuple(chosen) if len(chosen) > 1 else chosen[0]


# ---------------------------------------------------------------------------
# meta-step jit assembly (donation + shardings)
# ---------------------------------------------------------------------------


def meta_step_jit_kwargs(mcfg: MAvgConfig, state_shardings=None,
                         n_extra_args: int = 2,
                         donate_extra: tuple = (),
                         replicated=None) -> dict:
    """jax.jit kwargs for a ``step(state, batches, ...)`` meta step.

    One assembly point so every launcher agrees on the two coupled
    choices (DESIGN.md §10):

    * ``donate_argnums=(STATE_ARGNUM,)`` under ``mcfg.donate`` — the
      input MetaState's planes are aliased onto the output state's and
      updated in place, halving the meta phase's peak state HBM;
    * the state's in_shardings are the SAME object as its out_shardings.
      XLA only aliases a donated buffer whose input layout matches the
      output it is donated to, so a donated state must enter and leave
      the step under one sharding. (It also keeps the loop-carried
      layout stable across steps, donation or not.)

    ``n_extra_args`` counts the non-state positional args (batches, lr,
    and the telemetry ring under repro.obs). The batches keep the
    sharding they arrive with; the args after them and the step's
    non-state output take ``replicated`` (a replicated sharding on the
    state's mesh) when given, else stay unconstrained. Pinning them
    matters for loop-carried outputs such as the ring: left to the
    compiler, it comes back with another sharding than it went in with,
    and the next step compiles again. ``donate_extra`` names additional
    loop-carried argnums
    to donate regardless of ``mcfg.donate`` — the Trainer's on-device
    MetricsBuffer ring rides here (DESIGN.md §11): the caller never
    re-reads a pre-step ring, so its row write is always safe to do in
    place.
    """
    from repro.core.meta import STATE_ARGNUM

    kwargs = {}
    if state_shardings is not None:
        rest = (replicated,) * (n_extra_args - 1)
        kwargs["in_shardings"] = (state_shardings, None) + rest
        kwargs["out_shardings"] = (state_shardings, replicated)
    donate = ((STATE_ARGNUM,) if mcfg.donate else ()) + tuple(donate_extra)
    if donate:
        kwargs["donate_argnums"] = donate
    return kwargs


# ---------------------------------------------------------------------------
# state shardings (train)
# ---------------------------------------------------------------------------


def state_shardings(cfg: ModelConfig, mcfg: MAvgConfig, mesh, *,
                    hierarchical: bool = False,
                    tp_mode: str = "megatron") -> MetaState:
    """tp_mode:
    'megatron' — within-learner tensor parallelism over 'model' (heads /
        d_ff sharded; all-reduce of activations per layer).
    'fsdp' — weights fully sharded over 'model' on their largest dim and
        the learner's local batch sharded over 'model' (ZeRO-3 style:
        per-layer weight all-gather instead of activation all-reduce —
        wins when B*S >> d_model).
    """
    laxes = meshlib.learner_axes(mesh, hierarchical=hierarchical)
    fsdp = meshlib.fsdp_axes(mesh, hierarchical=hierarchical)
    params = abstract_params(cfg)
    if getattr(mcfg, "packed", False):
        return _packed_state_shardings(cfg, mcfg, mesh, params, laxes, tp_mode)
    if tp_mode == "dp":
        # paper-faithful extreme: one learner per CHIP, weights replicated
        # per learner — the only communication is the meta average (the
        # quantity the paper's K amortises). Only for models that fit one
        # chip (qwen3-1.7b-class).
        laxes = tuple(mesh.axis_names)
        gp_specs = make_param_specs(params, mesh, model_axis=None)
    elif tp_mode == "fsdp":
        gp_specs = make_param_specs(params, mesh, model_axis=None,
                                    fsdp_axis="model")
    else:
        gp_specs = make_param_specs(params, mesh, model_axis="model",
                                    fsdp_axis=fsdp)
    learner_specs = add_learner_axis(gp_specs, laxes if len(laxes) > 1 else laxes[0])
    n = lambda t: jax.tree.map(
        lambda s: NamedSharding(mesh, s), t, is_leaf=lambda x: isinstance(x, P)
    )
    from repro.comm import uses_error_feedback

    # EF residual is per-learner f32 with the learners' shapes -> same specs
    comm_sh = n(learner_specs) if uses_error_feedback(mcfg) else None

    # topology buffers (MetaState.topo): mirror the structure init_state
    # allocates. Gossip's params/momentum stacks and the async server's
    # anchor plane are (L, ...) like the learners and shard the same way;
    # everything else (G-leading hierarchical stacks, EF residual stacks,
    # (L,) clocks) stays replicated — small, or the axis rarely matches
    # a mesh axis size.
    from repro.core.meta import init_state as _init_state

    topo_abs = jax.eval_shape(
        lambda p: _init_state(p, mcfg), abstract_params(cfg)
    ).topo
    topo_sh = None
    if topo_abs is not None:
        topo_sh = jax.tree.map(
            lambda _: NamedSharding(mesh, P()), topo_abs
        )
        if mcfg.topology.kind == "gossip":
            topo_sh["params"] = n(learner_specs)
            topo_sh["momentum"] = n(learner_specs)
        if "anchor" in (topo_sh or {}):
            topo_sh["anchor"] = n(learner_specs)

    return MetaState(
        global_params=n(gp_specs),
        momentum=n(gp_specs),
        learners=n(learner_specs),
        local_momentum=None,
        step=NamedSharding(mesh, P()),
        comm_residual=comm_sh,
        topo=topo_sh,
    )


def _packed_state_shardings(cfg: ModelConfig, mcfg: MAvgConfig, mesh, params,
                            laxes, tp_mode: str) -> MetaState:
    """Shardings for the packed flat meta-plane (repro.pack, DESIGN.md §9).

    Every plane is one (rows, 128) buffer (or a (lead, rows, 128) stack),
    so per-leaf tensor-parallel specs don't apply; instead the packed row
    dimension is sharded over 'model' when it divides cleanly (each shard
    keeps the 8-row sublane multiple) — ZeRO-style: the local phase's
    unpack gathers what its matmuls need, the meta phase stays sharded.
    The learner axis of stacked planes shards over the learner mesh axes
    exactly as per-leaf learners did. The returned MetaState carries the
    same static PackSpec as the live state, so jit in_shardings matches
    structurally.
    """
    from repro.pack import make_pack_spec

    spec = make_pack_spec(params, dtype=mcfg.meta_dtype)
    if tp_mode == "dp":
        laxes = tuple(mesh.axis_names)
    lax_spec = laxes if len(laxes) > 1 else laxes[0]
    row_ax = None
    if (tp_mode != "dp" and "model" in mesh.shape
            and spec.rows % (mesh.shape["model"] * 8) == 0):
        row_ax = "model"
    ns = lambda *s: NamedSharding(mesh, P(*s))
    plane = ns(row_ax, None)  # (rows, 128) meta planes
    stacked = ns(lax_spec, row_ax, None)  # (L, rows, 128) learner planes

    from repro.comm import uses_error_feedback

    topo_abs = jax.eval_shape(
        lambda p: init_state(p, mcfg), params
    ).topo
    topo_sh = None
    if topo_abs is not None:
        # hierarchical (G, ...) stacks replicated (G is small and rarely
        # matches a mesh axis); gossip per-learner stacks and the async
        # server's (L, rows, 128) anchor plane shard like the learners
        topo_sh = jax.tree.map(lambda _: ns(), topo_abs)
        if mcfg.topology.kind == "gossip":
            topo_sh["params"] = stacked
            topo_sh["momentum"] = stacked
        if "anchor" in topo_sh:
            topo_sh["anchor"] = stacked

    return MetaState(
        global_params=plane,
        momentum=plane,
        learners=stacked,
        local_momentum=None,
        step=ns(),
        comm_residual=stacked if uses_error_feedback(mcfg) else None,
        topo=topo_sh,
        spec=spec,
    )


# ---------------------------------------------------------------------------
# serve (prefill) inputs
# ---------------------------------------------------------------------------


def prefill_input_specs(cfg: ModelConfig, shape: InputShape) -> dict:
    out = {}
    for name, (shp, dtype) in model_api.batch_shapes(
        cfg, shape.global_batch, shape.seq_len
    ).items():
        if name == "labels":
            continue
        out[name] = sds(shp, dtype)
    return out


def prefill_input_shardings(cfg: ModelConfig, mesh, shape: InputShape) -> dict:
    baxes = _batch_axes(mesh, shape.global_batch)
    specs = {}
    for name, (shp, _dt) in model_api.batch_shapes(
        cfg, shape.global_batch, shape.seq_len
    ).items():
        if name == "labels":
            continue
        specs[name] = NamedSharding(mesh, P(baxes, *([None] * (len(shp) - 1))))
    return specs


SERVE_FSDP_ENABLED = True  # flip via launchers for perf comparison


def serve_param_shardings(cfg: ModelConfig, mesh):
    params = abstract_params(cfg)
    fsdp = None
    if SERVE_FSDP_ENABLED and cfg.param_count() > SERVE_FSDP_THRESHOLD:
        fsdp = ("pod", "data") if "pod" in mesh.shape else "data"
    specs = make_param_specs(params, mesh, model_axis="model", fsdp_axis=fsdp)
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), specs, is_leaf=lambda x: isinstance(x, P)
    )


# ---------------------------------------------------------------------------
# decode inputs (one token + cache)
# ---------------------------------------------------------------------------


def decode_input_specs(cfg: ModelConfig, shape: InputShape):
    cache = jax.eval_shape(
        partial(model_api.init_cache, cfg, shape.global_batch, shape.seq_len)
    )
    tokens = sds((shape.global_batch,), jnp.int32)
    return cache, tokens


def cache_shardings(cfg: ModelConfig, mesh, shape: InputShape):
    """Family-specific KV-cache / recurrent-state placement (DESIGN.md §6)."""
    baxes = _batch_axes(mesh, shape.global_batch)
    msize = mesh.shape["model"]

    def ns(*spec):
        return NamedSharding(mesh, P(*spec))

    if cfg.family in ("dense", "moe", "vlm", "audio"):
        S = shape.seq_len
        seq_ax = "model" if S % msize == 0 else None
        return {
            "k": ns(None, baxes, seq_ax, None, None),
            "v": ns(None, baxes, seq_ax, None, None),
            "pos": ns(),
        }
    if cfg.family == "hybrid":
        W = min(cfg.sliding_window or shape.seq_len, shape.seq_len)
        win_ax = "model" if W % msize == 0 else None
        d_in_ok = (cfg.ssm_expand * cfg.d_model) % msize == 0
        din_ax = "model" if d_in_ok else None
        return {
            "k": ns(None, baxes, win_ax, None, None),
            "v": ns(None, baxes, win_ax, None, None),
            "k_meta": ns(None, baxes, None, None, None),
            "v_meta": ns(None, baxes, None, None, None),
            "conv": ns(None, baxes, None, din_ax),
            "ssm": ns(None, baxes, din_ax, None),
            "pos": ns(),
        }
    if cfg.family == "ssm":
        d_in = cfg.ssm_expand * cfg.d_model
        hd_m = d_in // cfg.num_heads  # mLSTM head dim
        hd_s = cfg.d_model // cfg.num_heads  # sLSTM head dim
        m_ax = "model" if hd_m % msize == 0 else None
        s_ax = "model" if hd_s % msize == 0 else None
        return {
            "m": (
                ns(None, None, baxes, None, None, m_ax),  # C (G,M,B,nh,hd,hd)
                ns(None, None, baxes, None, m_ax),  # n (G,M,B,nh,hd)
                ns(None, None, baxes, None),  # m (G,M,B,nh)
                ns(None, None, baxes, None, din_ax := (
                    "model" if d_in % msize == 0 else None
                )),  # conv buffer (G,M,B,k-1,d_in)
            ),
            "s": (
                ns(None, baxes, None, s_ax),  # c (G,B,nh,hd)
                ns(None, baxes, None, s_ax),
                ns(None, baxes, None, s_ax),
                ns(None, baxes, None, s_ax),
            ),
            "pos": ns(),
        }
    raise ValueError(cfg.family)


def decode_token_sharding(mesh, shape: InputShape):
    baxes = _batch_axes(mesh, shape.global_batch)
    return NamedSharding(mesh, P(baxes))
