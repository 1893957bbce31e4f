"""The Topology protocol: who averages with whom, how often.

The paper's meta step is one *flat* all-reduce every K local steps —
every learner averages with every other learner. This subsystem makes
that structure a first-class, swappable object (DESIGN.md §7), the same
way ``repro.comm`` did for what goes on the wire:

    mix(learners, gp, v, comm_residual, topo, step=n)
        -> (gp', v', learners', comm_residual', topo', metrics)

``learners`` is the stacked (L, ...) learner pytree after the K local
steps; ``gp``/``v`` are the meta params w~ and block momentum; ``topo``
is the topology's own buffer pytree riding in ``MetaState.topo`` (group
params/momentum for Hierarchical, per-learner params/momentum for
Gossip; None for flat). Each topology owns its Reducer(s), so every edge
class can carry its own compression scheme — dense intra-group,
int8_topk cross-group is where the inter-node byte savings land.

Topologies are built once per trace by ``make_topology`` (see
``repro.topology``), which also resolves the *effective* block-momentum
coefficient (kavg is mavg with mu forced to 0 — Remark 2) at
construction instead of per meta_step call.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import MAvgConfig
# the packed-plane dispatch predicate lives with the kernels it routes to
# (same layout constants) — re-exported here for the topologies
from repro.kernels.ops import is_packed_plane
from repro.utils import (
    tree_broadcast_learners,
    tree_cast,
    tree_norm,
    tree_sub,
)


def effective_momentum(cfg: MAvgConfig) -> float:
    """mu actually applied by the meta update: kavg is mavg with mu = 0."""
    return 0.0 if cfg.algorithm == "kavg" else cfg.momentum


def block_momentum_update(gp, v, avg, *, mu, eta=1.0, nesterov=False,
                          use_pallas=False):
    """v <- mu v + eta d ; w~ <- w~ + v  (+ optional Nesterov lookahead).

    Works on plain pytrees and on (G, ...)/(L, ...) stacked trees — the
    update is elementwise. ``use_pallas`` routes through the fused
    single-HBM-pass kernel (kernels/block_momentum.py).
    """
    import jax.numpy as jnp

    if use_pallas:
        from repro.kernels import ops as kops

        return kops.block_momentum_tree(
            gp, v, avg, mu=mu, eta=eta, nesterov=nesterov
        )
    d = tree_sub(avg, gp)
    v = jax.tree.map(lambda vi, di: mu * vi + eta * di, v, d)
    if nesterov:
        gp = jax.tree.map(
            lambda w, vi, di: w + mu * vi + eta * di, gp, v, d
        )
    else:
        gp = jax.tree.map(jnp.add, gp, v)
    return gp, v


def learner_dtype(learners):
    return jax.tree.leaves(learners)[0].dtype




def fused_momentum_broadcast_update(gp, v, avg, *, mu, eta, num_learners,
                                    ldtype, nesterov=False,
                                    use_pallas=False):
    """The packed meta plane's whole meta update in one pass: block
    momentum + the (L, rows, 128) learner-reset broadcast emitted
    directly from the update (kernels/fused_meta.py) instead of
    re-reading w~' through tree_broadcast_learners — one full-plane HBM
    read fewer per meta step (DESIGN.md §10). Bit-identical to
    ``block_momentum_update`` followed by cast + broadcast.

    Returns (gp', v', learners).

    Under a device mesh in context (``jax.set_mesh``, as the Trainer sets
    it for a sharded run) the Pallas kernel runs inside ``shard_map``:
    XLA cannot partition a Mosaic kernel. Each device updates the whole
    (replicated) meta plane and emits only the reset planes of the
    learners it holds on the mesh's learner axes.
    """
    from repro.kernels import ops as kops

    update = partial(
        kops.fused_momentum_broadcast, mu=mu, eta=eta, ldtype=ldtype,
        nesterov=nesterov, use_pallas=use_pallas,
    )
    mesh = jax.sharding.get_abstract_mesh()
    if not use_pallas or mesh.empty or mesh.size == 1:
        return update(gp, v, avg, num_learners=num_learners)
    from repro.launch.mesh import learner_axes

    axes = learner_axes(mesh)
    per_device = num_learners // math.prod(mesh.shape[a] for a in axes)
    return jax.shard_map(
        partial(update, num_learners=per_device), mesh=mesh,
        in_specs=(P(), P(), P()), out_specs=(P(), P(), P(axes)),
        check_vma=False,
    )(gp, v, avg)


class Topology:
    """Base: one meta-level mixing step over the learner stack.

    Synchrony itself is part of the protocol (DESIGN.md §12): the clock
    hooks below describe *when* learners reach their K-step boundary.
    Synchronous topologies are the tau=0 degenerate case — every learner
    fires every meta tick — which is what the defaults encode; the async
    bounded-staleness server (``topology/async_server.py``) overrides
    them with a deterministic per-learner step-time profile.
    """

    name = "topology"

    def init_buffers(self, gp, cfg: MAvgConfig) -> tuple[Any, Any]:
        """(comm_residual, topo) buffers for MetaState (None = unused)."""
        return None, None

    def fire_mask(self, topo, step):
        """(L,) bool: which learners push a finished K-step block at this
        meta tick. None = all of them (the synchronous barrier)."""
        return None

    def work_completed(self, step) -> int:
        """Cumulative K-step blocks completed through meta step ``step``
        (host-side, deterministic): the trainer's effective-samples
        accounting. Synchronous topologies complete L blocks per tick."""
        cfg = getattr(self, "cfg", None)
        return (int(step) + 1) * (cfg.num_learners if cfg is not None else 1)

    def local_steps(self, topo, step):
        """(L,) int32 active local-step counts for this meta step, or None
        when every learner runs the full cfg.k_steps.

        Heterogeneous execution hooks in here: per-group K_g (hierarchical
        ``group_k``) and elastic membership (absent learners run zero
        steps) both reduce to masking trailing iterations of the static
        K-step scan in ``core.meta._local_phase`` — the SPMD program never
        changes shape. ``step`` may be traced (membership is step-indexed).
        """
        return None

    def mix(self, learners, gp, v, comm_residual, topo, *, step):
        raise NotImplementedError


class FlatAllReduce(Topology):
    """Current behavior, extracted: one global average + block momentum.

    All traffic is a single all-reduce over every learner — under the
    wire model every byte crosses the slow inter-node links.
    """

    name = "flat"

    def __init__(self, cfg: MAvgConfig, reducer=None):
        from repro.comm import make_reducer
        from repro.robust import make_robust

        self.cfg = cfg
        self.mu = effective_momentum(cfg)
        self.robust = make_robust(cfg)
        agg = (
            self.robust.aggregate
            if self.robust is not None and self.robust.aggregates else None
        )
        self.reducer = (
            make_reducer(cfg, aggregate=agg) if reducer is None else reducer
        )

    def init_buffers(self, gp, cfg: MAvgConfig):
        return self.reducer.init_residual(gp, cfg.num_learners), None

    def mix(self, learners, gp, v, comm_residual, topo, *, step):
        cfg = self.cfg
        metrics = {}
        if self.robust is not None:
            # score + norm-clip the displacement stack BEFORE the reducer:
            # the wire compressor (and so the EF residual) only ever sees
            # the clipped displacement — clipped-away mass is rejected,
            # not deferred (DESIGN.md §14)
            learners, topo, rmetrics = self.robust.clip_learners(
                learners, gp, topo
            )
            metrics.update(rmetrics)
        avg, comm_residual, comm_metrics = self.reducer.reduce(
            learners, gp, comm_residual, step=step
        )
        avg = tree_cast(avg, cfg.meta_dtype)
        # pre-reset learner consensus: how far the K local steps drove the
        # learners apart before this average pulled them back — the
        # quantity the K/mu trade-off analyses bound (telemetry, DESIGN.md
        # §11; after the reset below consensus is identically zero)
        consensus = tree_norm(
            jax.tree.map(lambda w, a: w.astype(jnp.float32) - a[None],
                         learners, tree_cast(avg, jnp.float32))
        )
        if is_packed_plane(gp):
            # packed meta plane: momentum + learner reset in one pass
            gp_new, v, learners = fused_momentum_broadcast_update(
                gp, v, avg, mu=self.mu, eta=cfg.meta_lr,
                num_learners=cfg.num_learners,
                ldtype=learner_dtype(learners), nesterov=cfg.nesterov,
                use_pallas=cfg.use_pallas,
            )
        else:
            gp_new, v = block_momentum_update(
                gp, v, avg, mu=self.mu, eta=cfg.meta_lr,
                nesterov=cfg.nesterov, use_pallas=cfg.use_pallas,
            )
            learners = tree_broadcast_learners(
                tree_cast(gp_new, learner_dtype(learners)), cfg.num_learners
            )
        metrics.update({
            "v_norm": tree_norm(v),
            "displacement_norm": tree_norm(tree_sub(avg, gp)),
            "consensus_dist": consensus,
        })
        metrics.update(comm_metrics)
        return gp_new, v, learners, comm_residual, topo, metrics
