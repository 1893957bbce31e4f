"""Mixture-of-Experts layer: fine-grained routed experts + shared experts.

Covers DeepSeekMoE (2 shared + 64 routed, top-6) and Kimi-K2
(1 shared + 384 routed, top-8).

Dispatch uses the capacity-bounded gather/scatter pattern: tokens are
assigned positions inside their expert's capacity buffer with a cumsum
over the routing one-hot; the expert dimension is sharded over the
``model`` mesh axis (expert parallelism), so the gather/scatter lowers to
the all-to-all-style collectives a real MoE deployment performs, while the
expert matmuls stay local.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.layers import _dense_init, init_mlp, swiglu


# Expert-parallel sharding annotations. None = pure data flow (CPU tests);
# the launchers set this to 'model' so the dispatch gather / combine
# scatter keep the expert dim pinned to the tensor-parallel mesh axis.
EXPERT_AXIS = None

# Manual expert parallelism via shard_map (serving paths only — shard_map
# does not compose with the learner vmap in this JAX version, measured in
# EXPERIMENTS.md §Perf C). Each shard computes ONLY its local experts from
# the replicated token block and contributes a partial (T, d) psum:
# communication = one psum per layer, no replicate-reshard fallbacks.
SHARD_MAP_MESH = None  # set by launchers to the active Mesh


def set_expert_axis(axis, mesh=None):
    global EXPERT_AXIS, SHARD_MAP_MESH
    EXPERT_AXIS = axis
    SHARD_MAP_MESH = mesh


def _constrain_experts(x, spec=None):
    """Pin the expert dim to the tensor-parallel mesh axis.

    The capacity gather's output sharding is ambiguous to GSPMD (indices
    sharded on E, source replicated); left alone it replicates x_e and
    then ALL-GATHERS the expert weights per layer (~34 GB/layer for
    kimi-k2 — measured, EXPERIMENTS.md §Perf). No-op unless a launcher
    called set_expert_axis.
    """
    if EXPERT_AXIS is None:
        return x
    if spec is None:
        spec = (EXPERT_AXIS,) + (None,) * (x.ndim - 1)
    return jax.lax.with_sharding_constraint(x, P(*spec))


def init_moe(key, cfg: ModelConfig) -> dict:
    d, E, de = cfg.d_model, cfg.num_experts, cfg.d_expert
    ks = jax.random.split(key, 4)
    p = {
        "router": _dense_init(ks[0], (d, E), d),
        "w_in": _dense_init(ks[1], (E, d, 2, de), d),  # [gate, up] per expert
        "w_out": _dense_init(ks[2], (E, de, d), de),
    }
    if cfg.num_shared_experts:
        p["shared"] = init_mlp(ks[3], d, cfg.num_shared_experts * de)
    return p


def _capacity(num_tokens: int, cfg: ModelConfig, dropless: bool = False) -> int:
    """Per-expert capacity. ``dropless=True`` (serving paths) sizes the
    buffer for the worst case so no token is ever dropped: batched
    prefill logits then match token-by-token decode exactly
    (tests/test_decode_consistency.py), which capacity dropping breaks (a
    drop depends on the *other* tokens in the batch). top_k indices are
    distinct per token, so one expert receives at most ``num_tokens``
    slots — that bound, not num_tokens * k, keeps the dispatch buffer
    E x T instead of E x T*k (ragged dropless dispatch to shrink this
    further is a ROADMAP open item)."""
    if dropless:
        c = num_tokens
    else:
        c = int(num_tokens * cfg.moe_top_k * cfg.capacity_factor / cfg.num_experts)
    return max(8, -(-c // 8) * 8)  # round up to 8, floor of 8


def _route(xt, p, cfg: ModelConfig, dropless: bool = False):
    """Router + capacity assignment (shared by both execution paths).

    Returns (gates (T,k), slot_expert (T*k,), pos_clamped, keep, aux).
    """
    T, d = xt.shape
    E, k = cfg.num_experts, cfg.moe_top_k
    dt = xt.dtype
    logits = jnp.einsum("td,de->te", xt, p["router"].astype(dt)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, k)  # (T, k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

    # load-balance auxiliary loss (Switch-style)
    me = probs.mean(0)  # (E,)
    ce = jnp.zeros((E,), jnp.float32).at[idx.reshape(-1)].add(1.0) / (T * k)
    aux = E * jnp.sum(me * ce) * cfg.moe_aux_coef

    # dispatch: slot s = (t, j) -> (expert, position-in-capacity)
    C = _capacity(T, cfg, dropless)
    slot_expert = idx.reshape(-1)  # (T*k,)
    onehot = jax.nn.one_hot(slot_expert, E, dtype=jnp.int32)  # (T*k, E)
    pos_in_e = (jnp.cumsum(onehot, axis=0) * onehot).sum(-1) - 1  # (T*k,)
    keep = pos_in_e < C
    pos_clamped = jnp.minimum(pos_in_e, C - 1)
    return gates, slot_expert, pos_clamped, keep, aux, C


def moe_layer(x, p, cfg: ModelConfig, dropless: bool = False):
    """x: (B, S, d) -> (out (B, S, d), aux_loss scalar)."""
    B, S, d = x.shape
    E, k = cfg.num_experts, cfg.moe_top_k
    T = B * S
    xt = x.reshape(T, d)
    dt = x.dtype

    gates, slot_expert, pos_clamped, keep, aux, C = _route(xt, p, cfg, dropless)

    if SHARD_MAP_MESH is not None and EXPERT_AXIS is not None:
        out = _experts_shard_map(
            xt, p, cfg, gates, slot_expert, pos_clamped, keep, C
        )
        if cfg.num_shared_experts:
            out = out + swiglu(xt, p["shared"])
        return out.reshape(B, S, d), aux

    token_of_slot = jnp.repeat(jnp.arange(T), k)
    # scatter token ids into the (E, C) dispatch table; sentinel T = empty.
    # Dropped (over-capacity) slots scatter out of range (mode='drop') —
    # they must NOT write, or they'd overwrite the slot that exactly
    # fills the capacity (duplicate-index scatter order is unspecified).
    flat = jnp.where(keep, slot_expert * C + pos_clamped, E * C)
    dispatch = (
        jnp.full((E * C,), T, jnp.int32)
        .at[flat].set(token_of_slot, mode="drop")
        .reshape(E, C)
    )

    # gate weights laid out like the dispatch table (E, C)
    gate_tab = jnp.zeros((E * C,), jnp.float32).at[flat].set(
        gates.reshape(-1), mode="drop"
    ).reshape(E, C)

    xt_pad = jnp.concatenate([xt, jnp.zeros((1, d), dt)], axis=0)
    x_e = xt_pad[dispatch]  # (E, C, d) — expert-parallel gather
    x_e = _constrain_experts(x_e)

    h = jnp.einsum("ecd,edtf->ectf", x_e, p["w_in"].astype(dt))
    h = jax.nn.silu(h[..., 0, :]) * h[..., 1, :]
    y_e = jnp.einsum("ecf,efd->ecd", h, p["w_out"].astype(dt))  # (E, C, d)
    y_e = _constrain_experts(y_e)

    # ---- combine: scatter-add on the expert shards ----
    # Each expert shard accumulates its C tokens into a partial (T, d)
    # buffer; under GSPMD (E sharded over 'model') this lowers to one
    # all-reduce of (T, d) instead of a replicated (T*k, d) gather +
    # segment-sum (perf iteration for kimi-k2, EXPERIMENTS.md §Perf).
    y_w = y_e * gate_tab[..., None].astype(dt)  # (E, C, d)
    out = jnp.zeros((T + 1, d), dt).at[dispatch.reshape(-1)].add(
        y_w.reshape(E * C, d), mode="drop"
    )[:T]
    # the combined tokens are replicated again (one all-reduce over the
    # expert axis); keep the exchange in the compute dtype
    if EXPERT_AXIS is not None:
        out = jax.lax.with_sharding_constraint(out, P(None, None))

    if cfg.num_shared_experts:
        out = out + swiglu(xt, p["shared"])
    return out.reshape(B, S, d), aux


def _experts_shard_map(xt, p, cfg: ModelConfig, gates, slot_expert,
                       pos_clamped, keep, C):
    """Manual expert parallelism (serving paths).

    Each 'model'-axis shard owns E/n_shards experts; it dispatches the
    replicated token block to its local experts, runs the FFNs locally,
    and contributes a partial (T, d) output — combined with ONE psum.
    Communication per layer = one (T, d) all-reduce, versus the GSPMD
    gather/scatter path's replicate-reshard fallbacks (EXPERIMENTS.md
    §Perf C).
    """
    T, d = xt.shape
    E, k = cfg.num_experts, cfg.moe_top_k
    dt = xt.dtype
    mesh = SHARD_MAP_MESH
    n_shards = mesh.shape[EXPERT_AXIS]
    E_loc = E // n_shards
    P_ = P

    def local(xt, gates, slot_expert, pos_clamped, keep, w_in, w_out):
        shard = jax.lax.axis_index(EXPERT_AXIS)
        lo = shard * E_loc
        mine = keep & (slot_expert >= lo) & (slot_expert < lo + E_loc)
        flat = jnp.where(
            mine, (slot_expert - lo) * C + pos_clamped, E_loc * C
        )
        # slot s holds token s // k (an iota, not jnp.repeat: inside
        # shard_map repeat needs an explicit out_sharding)
        token_of_slot = jnp.arange(T * k, dtype=jnp.int32) // k
        dispatch = (
            jnp.full((E_loc * C,), T, jnp.int32)
            .at[flat].set(token_of_slot, mode="drop")
            .reshape(E_loc, C)
        )
        gate_tab = jnp.zeros((E_loc * C,), jnp.float32).at[flat].set(
            gates.reshape(-1), mode="drop"
        ).reshape(E_loc, C)
        xt_pad = jnp.concatenate([xt, jnp.zeros((1, d), dt)], axis=0)
        x_e = xt_pad[dispatch]  # (E_loc, C, d)
        h = jnp.einsum("ecd,edtf->ectf", x_e, w_in.astype(dt))
        h = jax.nn.silu(h[..., 0, :]) * h[..., 1, :]
        y_e = jnp.einsum("ecf,efd->ecd", h, w_out.astype(dt))
        y_w = y_e * gate_tab[..., None].astype(dt)
        part = jnp.zeros((T + 1, d), dt).at[dispatch.reshape(-1)].add(
            y_w.reshape(E_loc * C, d), mode="drop"
        )[:T]
        # psum in f32: XLA CPU's AllReducePromotion pass check-fails on
        # bf16 all-reduce (hlo_instruction.cc "Invalid binary opcode copy")
        return jax.lax.psum(part.astype(jnp.float32), EXPERT_AXIS).astype(dt)

    rep = P_()
    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P_(None, None), P_(None, None), rep, rep, rep,
                  P_(EXPERT_AXIS), P_(EXPERT_AXIS)),
        out_specs=P_(None, None),
        axis_names={EXPERT_AXIS},
    )
    return fn(xt, gates, slot_expert, pos_clamped, keep, p["w_in"], p["w_out"])
