"""Fused robust reduction of the packed learner stack — coordinate-wise
trimmed mean / median over the L axis in a single HBM pass — as a Pallas
TPU kernel (DESIGN.md §14).

The trusting meta average reads the (L, rows, 128) learner plane once and
sums it; the robust estimators need an order statistic per coordinate
(sort L values, drop the ``trim`` largest and smallest, average the
rest). Done naively that is a full-plane sort materialized in HBM plus a
second reduction pass. This kernel streams one (L, block, 128) VMEM tile
per grid step — the whole learner axis is resident, which is exactly why
the learner axis is the leading one in the packed layout — sorts along L
in-register (a min/max sorting network), and writes only the (block, 128)
aggregate: one read of the stack, one write of the result, and XLA cannot
re-split it.

``trim=0`` takes a static branch that skips the sort entirely and emits
``sum / L`` in the same reduction order as ``jnp.mean(x, axis=0)`` — the
bitwise ``trim=0 == mean`` parity every existing topology/async/elastic
invariant rides on (pinned in tests/test_robust.py). The jnp oracle
(ref.robust_reduce_ref) shares the op order, so kernel and reference
agree bit-for-bit in interpret mode and to float-associativity on TPU.

The coordinate-wise median is the maximal trim: ``trim = (L - 1) // 2``
leaves one value for odd L and the mean of the two middle values for
even L — callers resolve it via ``median_trim``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_ROWS = 64
LANES = 128


def median_trim(L: int) -> int:
    """The trim that turns the trimmed mean into the coordinate-wise
    median: keeps 1 value for odd L, the 2 middle values for even L."""
    return (L - 1) // 2


def _sort_learners(x):
    """Sort the static learner axis of an (L, block, 128) tile with an
    odd-even transposition network of elementwise min/max — the TPU
    kernel lowering has no sort. For finite inputs the result is exactly
    ``jnp.sort(x, axis=0)``."""
    v = [x[j] for j in range(x.shape[0])]
    for r in range(len(v)):
        for j in range(r % 2, len(v) - 1, 2):
            v[j], v[j + 1] = (jnp.minimum(v[j], v[j + 1]),
                              jnp.maximum(v[j], v[j + 1]))
    return jnp.stack(v)


def _kernel(x_ref, o_ref, *, trim: int):
    x = x_ref[...].astype(jnp.float32)  # (L, block, 128)
    L = x.shape[0]
    if trim == 0:
        # same reduction order as jnp.mean(x, axis=0): sum then divide —
        # the bitwise mean-parity contract
        o_ref[...] = jnp.sum(x, axis=0) / L
    else:
        s = _sort_learners(x)
        kept = jnp.sum(s[trim:L - trim], axis=0)
        o_ref[...] = kept / (L - 2 * trim)


def robust_reduce_3d(x, *, trim: int = 0, block: int | None = None,
                     interpret: bool = False):
    """x: (L, rows, 128) learner stack (any float dtype).

    Returns the (rows, 128) f32 coordinate-wise trimmed mean over the L
    axis: drop the ``trim`` largest and smallest values per coordinate,
    average the remaining ``L - 2*trim``.
    """
    L, rows, lanes = x.shape
    assert lanes == LANES and rows % 8 == 0, x.shape
    assert 0 <= 2 * trim < L, (trim, L)
    b = min(BLOCK_ROWS if block is None else block, rows)
    assert rows % b == 0, (rows, b)
    return pl.pallas_call(
        functools.partial(_kernel, trim=trim),
        grid=(rows // b,),
        in_specs=[pl.BlockSpec((L, b, LANES), lambda i: (0, i, 0))],
        out_specs=pl.BlockSpec((b, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
        interpret=interpret,
    )(x)
