"""The Reducer protocol: how the meta average crosses the wire.

The paper's communication model is one collective per K local steps; this
subsystem makes that collective an explicit, swappable object so its cost
can be modeled (bytes-on-wire metrics), measured (benchmarks/comm_bench),
and reduced (quantization / sparsification with error feedback).

    reduce(learners, gp, residual, step=n) -> (avg, residual', metrics)

``learners`` is the stacked (L, ...) learner pytree, ``gp`` the meta
params w~. Compressed reducers operate on the *displacements*
delta_j = w_j - w~ (small, zero-centred — far friendlier to 8-bit scales
than raw weights) and return avg = w~ + mean_j C(delta_j). ``residual``
is the per-learner error-feedback memory e_j carried in
``MetaState.comm_residual`` (None when EF is off); the EF invariant
(DESIGN.md §5) is

    delta_j + e_j = C(delta_j + e_j) + e'_j      (exactly, per leaf)

so compression error is re-injected next round and the block-momentum
update stays unbiased (Yu, Jin & Yang 2019, PAPERS.md).

Every reducer reports ``comm_bytes`` (modeled wire payload this step),
``comm_bytes_dense`` (what the dense scheme would ship) and
``comm_compression``; bytes are analytic — under SPMD simulation nothing
is physically serialized, but the *numerics* of compression are real
(values really are rounded to the wire grid / zeroed by top-k).
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.utils import (
    tree_add,
    tree_cast,
    tree_mean_axis0,
    tree_norm,
    tree_size,
    tree_sub,
)


def dense_bytes(learners) -> float:
    """Wire payload of the uncompressed meta average: every learner ships
    its full displacement at the learner dtype width."""
    return float(sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(learners)))


class Reducer:
    """Base: reduce the learner stack to one averaged parameter tree."""

    name = "reducer"
    # robust aggregation hook (repro.robust, DESIGN.md §14): a callable
    # replacing the trusting learner-stack mean (trimmed mean / median
    # over the L axis). None — the default, and the only value when
    # MAvgConfig.robust is off — keeps the exact mean code path.
    aggregate = None

    def init_residual(self, gp, num_learners: int):
        """Error-feedback state for MetaState.comm_residual (None = off)."""
        return None

    def reduce(self, learners, gp, residual, *, step) -> tuple[Any, Any, dict]:
        raise NotImplementedError


class DenseReducer(Reducer):
    """Today's exact behavior, extracted: a = mean_j w_j, full precision."""

    name = "dense"

    def __init__(self, meta_dtype: str = "float32"):
        self.meta_dtype = meta_dtype

    def reduce(self, learners, gp, residual, *, step):
        if self.aggregate is not None:
            avg = tree_cast(self.aggregate(learners), self.meta_dtype)
        else:
            avg = tree_cast(tree_mean_axis0(learners), self.meta_dtype)
        b = dense_bytes(learners)
        metrics = {
            "comm_bytes": b,
            "comm_bytes_dense": b,
            "comm_compression": 1.0,
        }
        return avg, residual, metrics


class CompressedReducer(Reducer):
    """Shared displacement/EF plumbing; subclasses supply ``_compress``."""

    def _compress(self, delta, step) -> tuple[Any, float]:
        """delta: (L, ...) f32 pytree -> (decompressed C(delta), wire bytes)."""
        raise NotImplementedError

    def _compress_residual(self, delta, step) -> tuple[Any, Any, float]:
        """``_compress`` plus the compression error err = delta - C(delta)
        of the same pass: (c, err, wire bytes).

        The error-feedback compress-only route (gossip neighbor exchange,
        masked hierarchical inner — topology.gossip.compress_stack) needs
        err as the next residual; deriving it here lets reducers whose
        kernel already computed delta - c in-register (QuantReducer on
        the packed plane, kernels/pack_update.py) hand it over without a
        second full-plane subtraction pass. The default is the two-pass
        fallback and is bitwise-identical to it by contract.
        """
        c, wire = self._compress(delta, step)
        return c, tree_sub(delta, c), wire

    def reduce(self, learners, gp, residual, *, step):
        delta = jax.tree.map(
            lambda w, g: w.astype(jnp.float32) - g.astype(jnp.float32)[None],
            learners, gp,
        )
        if residual is not None:
            delta = tree_add(delta, residual)
        c, wire = self._compress(delta, step)
        err = tree_sub(delta, c)  # quantization error: EF residual + metric
        new_residual = err if residual is not None else None
        if self.aggregate is not None:
            avg = tree_add(tree_cast(gp, jnp.float32), self.aggregate(c))
        else:
            avg = jax.tree.map(
                lambda g, ci: (g.astype(jnp.float32) + jnp.mean(ci, axis=0)),
                gp, c,
            )
        db = dense_bytes(learners)
        metrics = {
            "comm_bytes": wire,
            "comm_bytes_dense": db,
            "comm_compression": db / wire,
            "comm_error_norm": tree_norm(err),
        }
        return avg, new_residual, metrics


class ErrorFeedback(Reducer):
    """Wrapper carrying the compression residual e_j across meta steps.

    Supplies a non-None ``init_residual`` so ``MetaState.comm_residual``
    has a stable pytree structure from step 0 (jit/checkpoint friendly);
    the residual algebra itself lives in CompressedReducer.reduce, keyed
    on residual presence.
    """

    def __init__(self, inner: CompressedReducer):
        self.inner = inner

    @property
    def name(self):
        return f"ef+{self.inner.name}"

    def init_residual(self, gp, num_learners: int):
        return jax.tree.map(
            lambda x: jnp.zeros((num_learners,) + x.shape, jnp.float32), gp
        )

    def reduce(self, learners, gp, residual, *, step):
        if residual is None:
            raise ValueError(
                "ErrorFeedback.reduce got residual=None — the MetaState was "
                "built without this reducer's residual buffer. Pass the same "
                "reducer to init_state(params, cfg, reducer=...) that you "
                "inject into meta_step/make_meta_step."
            )
        return self.inner.reduce(learners, gp, residual, step=step)


def make_reducer(cfg, aggregate=None) -> Reducer:
    """Build the reducer described by ``cfg.comm`` (an MAvgConfig)."""
    return make_reducer_for(cfg.comm, meta_dtype=cfg.meta_dtype,
                            aggregate=aggregate)


def make_reducer_for(c, meta_dtype: str = "float32",
                     aggregate=None) -> Reducer:
    """Build a reducer from a bare ``CommConfig`` — the topology subsystem
    instantiates one per edge class (intra-group / cross-group / gossip
    neighbor), each with its own scheme. ``aggregate`` installs the
    robust aggregation hook (repro.robust) on the underlying reducer.
    An unset ``c.use_pallas`` is resolved from the platform here."""
    from dataclasses import replace

    from repro.comm.quant import QuantReducer
    from repro.comm.topk import TopKReducer
    from repro.kernels.ops import resolve_use_pallas

    c = replace(c, use_pallas=resolve_use_pallas(c.use_pallas))

    if c.scheme == "dense":
        r = DenseReducer(meta_dtype=meta_dtype)
        if aggregate is not None:
            r.aggregate = aggregate
        return r
    if c.scheme in ("int8", "fp8"):
        r = QuantReducer(dtype=c.scheme, chunk_rows=c.chunk_rows,
                         use_pallas=c.use_pallas, seed=c.seed)
    elif c.scheme == "topk":
        r = TopKReducer(k_frac=c.k_frac)
    elif c.scheme == "int8_topk":
        r = TopKReducer(k_frac=c.k_frac, quant_dtype="int8",
                        chunk_rows=c.chunk_rows, use_pallas=c.use_pallas,
                        seed=c.seed)
    else:
        raise ValueError(f"unknown comm scheme {c.scheme!r}")
    if aggregate is not None:
        r.aggregate = aggregate
    if c.error_feedback:
        return ErrorFeedback(r)
    return r


def uses_error_feedback(cfg) -> bool:
    """Does ``cfg`` (an MAvgConfig) carry an EF residual in
    ``MetaState.comm_residual``?

    The single source of truth for 'is comm_residual a pytree or None' —
    init_state and launch.specs.state_shardings must agree on it. Only
    the *flat* topology keeps its residual there; hierarchical/gossip
    carry theirs inside ``MetaState.topo`` (repro.topology owns the
    buffer layout), so comm_residual stays None for them.
    """
    from repro.configs.base import AVERAGING_ALGOS

    return (cfg.algorithm in AVERAGING_ALGOS
            and cfg.topology.kind == "flat"
            and cfg.comm.scheme != "dense" and cfg.comm.error_feedback)


def reducer_residual(params_or_gp, cfg):
    """comm_residual for init_state: None unless EF + a compressed scheme."""
    return make_reducer(cfg).init_residual(params_or_gp, cfg.num_learners)
