"""The paper's contribution: M-AVG (Algorithm 1) and its baselines, as one
composable meta-optimizer over an arbitrary loss function.

Algorithms
----------
mavg         Algorithm 1: K local SGD steps per learner, then
             a = mean_j w_j; d = a - w~; v = mu v + d; w~ += v; reset.
kavg         Zhou & Cong 2017 (the paper's baseline): mavg with mu = 0.
sync         synchronous MSGD: mavg with K = 1 (identical math, kept as an
             explicit alias so benchmarks can name it).
mavg_mlocal  beyond-paper / the paper's section-V note: learner-level MSGD
             inside the K-step loop, block momentum on top.
eamsgd       Zhang et al. 2015 elastic averaging with center momentum
             (the paper's strongest baseline in section IV) — an alias
             onto the async server's elastic update rule
             (repro.topology.async_server, DESIGN.md §12).
downpour     Dean et al. 2012, simulated with deterministic bounded
             staleness (true async is unexpressible under SPMD; staleness
             is the quantity the convergence analyses bound — DESIGN.md
             §4/§12) — an alias onto the async server's staleness-decayed
             update with decay 1.0.

This module contains NO per-algorithm meta-update branches: every
algorithm, legacy baselines included, routes through the Topology
protocol (repro.topology.make_topology resolves the aliases).

The learner dimension is a leading pytree axis of size L = P (the paper's
number of processors). Under pjit that axis is sharded over the mesh's
learner axes, so the K inner steps emit no cross-learner collectives and
the meta averaging is one all-reduce — the paper's communication model.
That all-reduce is owned by a pluggable ``repro.comm`` Reducer (dense /
int8 / fp8 / top-k, with optional error feedback whose residual rides in
``MetaState.comm_residual`` — DESIGN.md §5), selected via
``MAvgConfig.comm`` or injected into ``meta_step``/``make_meta_step``.
*Which* learners average with which, and how often, is owned by the
``repro.topology`` subsystem (flat all-reduce / hierarchical two-level
M-AVG / decentralized gossip — DESIGN.md §7), selected via
``MAvgConfig.topology``; its buffers ride in ``MetaState.topo``.

Under ``MAvgConfig.packed`` (the default) the whole meta plane is the
packed flat buffer of ``repro.pack`` (DESIGN.md §9): every state field is
one lane-aligned (rows, 128) array (stacked (L, rows, 128) along the
learner axis) and the model pytree exists only inside ``_local_phase``.
Because a raw array is itself a pytree, all the meta algebra below runs
unchanged on either representation — what changes is the cost: one
whole-model kernel pass per op instead of one per leaf.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import MAvgConfig
from repro.pack import make_pack_spec
from repro.utils import (
    tree_broadcast_learners,
    tree_cast,
    tree_norm,
    tree_zeros_like,
)

LossFn = Callable[..., tuple[jnp.ndarray, dict]]  # (params, batch) -> (loss, aux)


@jax.tree_util.register_dataclass
@dataclass
class MetaState:
    """Full state of the distributed trainer.

    global_params: w~ (meta dtype, f32)
    momentum:      v, the block-momentum buffer (mavg/eamsgd) or None
    learners:      stacked learner copies, leading axis L
    local_momentum: learner-level momentum stacks (mavg_mlocal) or None
    step:          meta iteration n
    comm_residual: per-learner error-feedback residual e_j of the comm
                   reducer (L, ...) f32, or None when EF is off
    topo:          topology buffer pytree (repro.topology — group params /
                   momentum under hierarchical, per-learner params /
                   momentum under gossip, logical clocks + anchor planes
                   under the async server), or None under flat
    spec:          STATIC repro.pack.PackSpec of the packed flat
                   meta-plane, or None on the legacy per-leaf path. When
                   set, every plane above is a single lane-aligned
                   (rows, 128) buffer (stacked (L, rows, 128) along the
                   learner axis) instead of a parameter pytree; the model
                   pytree exists only inside the local phase
                   (DESIGN.md §9). Static: part of the pytree structure,
                   not a leaf — jit caches on it and checkpoints skip it.
    """

    global_params: Any
    momentum: Any
    learners: Any
    local_momentum: Any
    step: jnp.ndarray
    comm_residual: Any = None
    topo: Any = None
    spec: Any = field(default=None, metadata=dict(static=True))


def init_state(params, cfg: MAvgConfig, reducer=None,
               topology=None) -> MetaState:
    """Meta state (w~, v) in cfg.meta_dtype (f32 — Theorem 1's momentum
    variance is precision-sensitive); learner copies in cfg.compute_dtype
    (bf16 on TPU: halves every weight collective and the L-fold copy
    memory; the meta average casts back up to f32).

    Pass the same ``reducer``/``topology`` you inject into
    meta_step/make_meta_step (if any) so the matching error-feedback /
    topology buffers are allocated; otherwise ``cfg.comm``/``cfg.topology``
    decide.

    Under ``cfg.packed`` (the default) the param pytree is packed once
    into the flat meta-plane here, and every state buffer below is a
    single (rows, 128) / (L, rows, 128) array; the static PackSpec rides
    in ``MetaState.spec`` so meta_step can unpack at the learner
    boundary and eval code can recover the model pytree
    (repro.pack.unpack_params).
    """
    spec = None
    if cfg.packed:
        spec = make_pack_spec(params, dtype=cfg.meta_dtype)
        params = spec.pack(params)
    # the state must OWN its buffers: a same-dtype astype is a no-op that
    # aliases the caller's param arrays, and under cfg.donate the jitted
    # step would then delete the caller's buffers with the donated state
    # (caught by tests/test_zero_copy.py). jnp.array copies
    # unconditionally; one extra whole-model copy, once per run.
    gp = jax.tree.map(
        lambda x: jnp.array(x, dtype=jnp.dtype(cfg.meta_dtype)), params
    )
    learners = tree_broadcast_learners(
        tree_cast(gp, cfg.compute_dtype), cfg.num_learners
    )
    if topology is None:
        from repro.topology import make_topology

        topology = make_topology(cfg, reducer)
    comm_residual, topo = topology.init_buffers(gp, cfg)
    if cfg.robust is not None and cfg.robust.clip_mult > 0.0:
        # the norm clip's trailing-median budget ring (repro.robust,
        # DESIGN.md §14) rides in MetaState.topo regardless of topology —
        # merged here so the layout changes only when the feature is on
        from repro.robust import robust_ring_buffers

        topo = {**(topo or {}), **robust_ring_buffers(cfg.robust)}
    return MetaState(
        global_params=gp,
        momentum=tree_zeros_like(gp),
        learners=learners,
        local_momentum=(
            tree_zeros_like(learners) if cfg.algorithm == "mavg_mlocal" else None
        ),
        step=jnp.zeros((), jnp.int32),
        comm_residual=comm_residual,
        topo=topo,
        spec=spec,
    )


# ---------------------------------------------------------------------------
# local phase: K SGD/MSGD steps per learner, no cross-learner communication
# ---------------------------------------------------------------------------


def _local_phase(loss_fn: LossFn, learners, local_mom, batches, cfg: MAvgConfig,
                 lr, steps=None, spec=None):
    """batches: pytree with leaves (L, K, B_local, ...).

    ``steps``: optional (L,) int32 active-step counts (heterogeneous
    per-group K_g / elastic membership — repro.topology): learner j
    applies only the first steps[j] of the K scanned updates, the rest
    are masked with ``where`` so the compiled SPMD program is identical
    for every schedule (an absent learner runs 0 steps). Loss/grad-norm
    means count active steps only. ``steps`` may be traced (membership
    is step-indexed).

    ``spec``: the packed meta-plane layout (repro.pack). The local phase
    is the ONLY place the model pytree exists under packing: each
    learner's (rows, 128) buffer is unpacked to the param tree here
    (loss_fn needs structure), the K-step scan runs on the tree exactly
    as on the per-leaf path (bit-identical update math), and the result
    is repacked once after the scan. Leaves stay in the learner plane's
    compute dtype through the round trip.

    Returns (new learners, new local momentum, mean loss, mean grad-norm,
    per-learner mean loss (L,)) — the per-learner vector feeds the
    ``loss_spread`` telemetry metric (repro.obs): data-heterogeneity and
    straggler divergence show up as spread before they show up in the
    mean.
    """
    if spec is not None:
        ldt = _ldtype(learners)
        # a None momentum (no learner-level momentum) stays None
        unpack = lambda b: None if b is None else spec.unpack(b, dtype=b.dtype)
        repack = lambda t: None if t is None else spec.pack(t, dtype=ldt)
    else:
        unpack = repack = lambda t: t
    # the learner's own update work, apart from the model's forward and
    # backward: grad norm, SGD step, and the unpack/repack of its planes
    update_scope = partial(jax.named_scope, "obs.learner_update")

    def sgd_update(w, mom, g):
        # update math in f32, stored back in the learner dtype (bf16
        # learner copies keep collectives/memory at half cost)
        if cfg.local_momentum > 0.0:
            mom = jax.tree.map(
                lambda m, gi: (
                    cfg.local_momentum * m.astype(jnp.float32)
                    - lr * gi.astype(jnp.float32)
                ).astype(m.dtype),
                mom, g,
            )
            w = jax.tree.map(
                lambda wi, m: (wi + m.astype(wi.dtype)), w, mom
            )
        else:
            w = jax.tree.map(
                lambda wi, gi: (
                    wi.astype(jnp.float32) - lr * gi.astype(jnp.float32)
                ).astype(wi.dtype),
                w, g,
            )
        return w, mom

    def one_learner(w, mom, bks):
        def step(carry, b):
            w, mom = carry
            (loss, _aux), g = jax.value_and_grad(loss_fn, has_aux=True)(w, b)
            with update_scope():
                gnorm = tree_norm(g)
                w, mom = sgd_update(w, mom, g)
            return (w, mom), (loss, gnorm)

        with update_scope():
            w, mom = unpack(w), unpack(mom)
        (w, mom), (losses, gnorms) = lax.scan(step, (w, mom), bks)
        with update_scope():
            w, mom = repack(w), repack(mom)
        return w, mom, losses.mean(), gnorms.mean()

    def one_learner_masked(w, mom, bks, s):
        k = jax.tree.leaves(bks)[0].shape[0]

        def step(carry, xs):
            w, mom = carry
            b, i = xs
            (loss, _aux), g = jax.value_and_grad(loss_fn, has_aux=True)(w, b)
            with update_scope():
                gnorm = tree_norm(g)
                w_upd, mom_upd = sgd_update(w, mom, g)
                keep = i < s
                w = jax.tree.map(lambda n, o: jnp.where(keep, n, o), w_upd, w)
                mom = jax.tree.map(
                    lambda n, o: jnp.where(keep, n, o), mom_upd, mom
                )
            return (w, mom), (loss, gnorm, keep.astype(jnp.float32))

        with update_scope():
            w, mom = unpack(w), unpack(mom)
        (w, mom), (losses, gnorms, act) = lax.scan(
            step, (w, mom), (bks, jnp.arange(k))
        )
        with update_scope():
            w, mom = repack(w), repack(mom)
        return (w, mom,
                (losses * act).sum(), (gnorms * act).sum(), act.sum())

    # without learner-level momentum the carry holds no momentum stack at
    # all (an all-zero (L, rows, 128) plane carried through the scan would
    # cost one learner-plane copy of memory and match the learners'
    # sharding only by accident); with local_momentum but no persistent
    # stack the momentum restarts from zero every block
    mom_in = local_mom
    if mom_in is None and cfg.local_momentum > 0.0:
        mom_in = tree_zeros_like(learners)
    over_learners = _in_sequence if cfg.sequential_learners else jax.vmap
    if steps is None:
        w, mom, loss_l, gnorm = over_learners(one_learner)(
            learners, mom_in, batches
        )
        loss, gnorm = loss_l.mean(), gnorm.mean()
    else:
        w, mom, lsum, gsum, asum = over_learners(one_learner_masked)(
            learners, mom_in, batches, steps
        )
        denom = jnp.maximum(asum.sum(), 1.0)
        loss, gnorm = lsum.sum() / denom, gsum.sum() / denom
        # per-learner mean over that learner's ACTIVE steps; an absent
        # learner (0 active steps) reports 0 and is masked out of the
        # spread metric by the caller via the active counts
        loss_l = lsum / jnp.maximum(asum, 1.0)
    active = None if steps is None else (asum > 0)
    return (w, (mom if local_mom is not None else None), loss, gnorm,
            loss_l, active)


def _in_sequence(fn):
    """``fn`` over the leading (learner) axis of its arguments, one slice
    after another (``lax.map``) — the counterpart of ``jax.vmap(fn)``."""
    return lambda *xs: lax.map(lambda x: fn(*x), xs)


def _learner_finite_mask(tree):
    """(L,) bool — True where every float element of learner j's planes is
    finite. None when the tree has no float leaves."""
    flags = None
    for x in jax.tree.leaves(tree):
        if not jnp.issubdtype(x.dtype, jnp.floating):
            continue
        ok = jnp.all(
            jnp.isfinite(x.astype(jnp.float32)).reshape(x.shape[0], -1),
            axis=1,
        )
        flags = ok if flags is None else (flags & ok)
    return flags


def _tree_where_learners(ok, new, old):
    """Leafwise select on the (L,) mask broadcast over trailing dims."""

    def sel(n, o):
        m = ok.reshape((n.shape[0],) + (1,) * (n.ndim - 1))
        return jnp.where(m, n, o)

    return jax.tree.map(sel, new, old)


def _finite_guard(learners, local_mom, gp, metrics, L):
    """The in-step skip-and-decay barrier (DESIGN.md §13): a learner whose
    post-local-phase planes (or local momentum) carry NaN/Inf is reset to
    the broadcast global params — zero displacement into the mix, so the
    poisoned block is skipped and (with every learner bad) the block
    momentum pure-decays — and its local momentum is zeroed. This is the
    structural guarantee that a non-finite value can never cross from the
    learner plane into ``MetaState.global_params``: the mean of finite
    planes is finite. On a clean step the mask is all-true and every
    ``where`` returns its first argument bitwise (pinned)."""
    ok = _learner_finite_mask(learners)
    if local_mom is not None:
        mok = _learner_finite_mask(local_mom)
        if mok is not None:
            ok = mok if ok is None else (ok & mok)
    if ok is None:
        return learners, local_mom, metrics
    clean = tree_broadcast_learners(tree_cast_like(gp, learners), L)
    learners = _tree_where_learners(ok, learners, clean)
    if local_mom is not None:
        zeros = jax.tree.map(jnp.zeros_like, local_mom)
        local_mom = _tree_where_learners(ok, local_mom, zeros)
    metrics["nonfinite_learners"] = (
        jnp.float32(L) - ok.sum().astype(jnp.float32)
    )
    return learners, local_mom, metrics


def tree_cast_like(tree, like):
    """``tree`` cast leafwise to the dtypes of ``like``'s leaves (shapes
    may differ — only dtype is taken)."""
    like_leaves = jax.tree.leaves(like)
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    return jax.tree_util.tree_unflatten(
        treedef,
        [x.astype(y.dtype) for x, y in zip(leaves, like_leaves)],
    )


def _loss_spread(loss_l, active):
    """max - min of the per-learner mean losses, over ACTIVE learners only
    (elastic membership: an absent learner ran 0 steps and reports no
    loss). 0 when fewer than one learner is active. The telemetry signal
    for data heterogeneity / straggler divergence (repro.obs)."""
    if active is None:
        return jnp.max(loss_l) - jnp.min(loss_l)
    hi = jnp.max(jnp.where(active, loss_l, -jnp.inf))
    lo = jnp.min(jnp.where(active, loss_l, jnp.inf))
    return jnp.where(jnp.any(active), hi - lo, 0.0)


# ---------------------------------------------------------------------------
# meta updates
# ---------------------------------------------------------------------------


def meta_step(state: MetaState, batches, *, loss_fn: LossFn, cfg: MAvgConfig,
              lr=None, reducer=None, topology=None,
              chaos=None) -> tuple[MetaState, dict]:
    """One meta-iteration n -> n+1 of Algorithm 1 (or a baseline).

    batches: pytree with leaves (L, K, B_local, ...) — K local mini-batches
    for each of the L learners. ``reducer`` overrides the comm scheme
    built from ``cfg.comm`` (repro.comm.make_reducer); ``topology``
    overrides the mixing structure built from ``cfg.topology``
    (repro.topology.make_topology). Prefer make_meta_step, which builds
    both once per trace.

    ``chaos``: optional payload corruptor (repro.chaos.PayloadCorruptor)
    called on the post-local-phase learner planes — the comm-layer fault
    injection point, placed exactly where the reducer picks the payload
    up. ``cfg.finite_guard`` then screens the (possibly corrupted)
    planes before the mix (see ``_finite_guard``).
    """
    lr = jnp.float32(cfg.learner_lr) if lr is None else lr
    if topology is None:
        from repro.topology import make_topology

        topology = make_topology(cfg, reducer)
    # synchrony is the topology's axis (DESIGN.md §12): it may mask
    # trailing local steps per learner (per-group K_g, elastic
    # membership) or mask whole K-blocks (the async server's clocks —
    # a learner runs its K steps only on the tick it fires)
    steps = topology.local_steps(state.topo, state.step)
    with jax.named_scope("obs.local_phase"):
        learners, local_mom, loss, gnorm, loss_l, active = _local_phase(
            loss_fn, state.learners, state.local_momentum, batches, cfg, lr,
            steps=steps, spec=state.spec,
        )
    gp, v = state.global_params, state.momentum
    comm_res = state.comm_residual
    topo = state.topo
    metrics = {
        "loss": loss,
        "grad_norm": gnorm,
        "loss_spread": _loss_spread(loss_l, active),
    }

    if chaos is not None:
        with jax.named_scope("chaos.payload"):
            learners = chaos(learners, state.step)
    if cfg.finite_guard:
        with jax.named_scope("chaos.finite_guard"):
            learners, local_mom, metrics = _finite_guard(
                learners, local_mom, gp, metrics, cfg.num_learners
            )

    with jax.named_scope("obs.meta_mix"):
        gp, v, learners, comm_res, topo, topo_metrics = topology.mix(
            learners, gp, v, comm_res, topo, step=state.step
        )
    metrics.update(topo_metrics)
    if state.spec is not None:
        # reducers see the packed plane and model their value bytes
        # over its element count, which includes alignment/tail
        # padding; rescale all byte metrics to the real parameter
        # count so packed and per-leaf runs report comparable wire
        # payloads (scale/index bytes are approximated by the same
        # factor — chunk geometry differs between layouts anyway)
        f = sum(state.spec.sizes) / state.spec.total
        for k in list(metrics):
            if k.startswith("comm_bytes"):
                metrics[k] = metrics[k] * f

    state = MetaState(
        global_params=gp, momentum=v, learners=learners,
        local_momentum=local_mom,
        step=state.step + 1, comm_residual=comm_res, topo=topo,
        spec=state.spec,
    )
    return state, metrics


def _ldtype(learners):
    return jax.tree.leaves(learners)[0].dtype


def make_meta_step(loss_fn: LossFn, cfg: MAvgConfig, reducer=None,
                   topology=None, chaos=None):
    """Returns a jit-able ``step(state, batches) -> (state, metrics)``.

    The topology (and through it the comm reducer(s), plus the effective
    block-momentum coefficient — kavg forces mu = 0) is resolved once
    here, not per meta_step call, so every trace reuses the same objects.
    ``chaos`` (a PayloadCorruptor or None) is likewise baked into the
    closure — its schedule arrays become jit constants.
    """
    if topology is None:
        from repro.topology import make_topology

        topology = make_topology(cfg, reducer)
    return partial(meta_step, loss_fn=loss_fn, cfg=cfg, topology=topology,
                   chaos=chaos)


# position of the MetaState argument in every ``step(state, batches, ...)``
# signature this repo jits — the single constant Trainer / launch/specs.py
# thread into jax.jit(donate_argnums=...)
STATE_ARGNUM = 0


def make_jit_meta_step(loss_fn: LossFn, cfg: MAvgConfig, reducer=None,
                       topology=None, chaos=None, *, donate=None,
                       **jit_kwargs):
    """``make_meta_step`` wrapped in ``jax.jit`` with MetaState donation.

    Under ``cfg.donate`` (override with ``donate=``) the input state is
    donated to the step: XLA aliases every (rows, 128) plane of the input
    MetaState to the corresponding output plane and updates it in place,
    so the meta phase holds ONE copy of the state live instead of two —
    the compiled meta mix's peak state at xlstm-350m's packed planes
    halves (DESIGN.md §10, tests/test_zero_copy.py ZC5). Numerics are
    unchanged: donation is pure buffer aliasing.

    The contract the caller signs: the state passed in is DEAD after the
    call (jax raises on re-use). Work off the returned state only —
    metrics, checkpointing, resume (core/trainer.py is the reference
    consumer). Extra ``jit_kwargs`` (in_shardings/out_shardings from
    launch/specs.py) pass through; the state's in_shardings must equal
    its out_shardings or XLA cannot alias the donated buffers.
    """
    step_fn = make_meta_step(loss_fn, cfg, reducer, topology, chaos)
    if cfg.donate if donate is None else donate:
        jit_kwargs.setdefault("donate_argnums", (STATE_ARGNUM,))
    return jax.jit(step_fn, **jit_kwargs)
