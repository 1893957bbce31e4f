"""Fused block-momentum + learner-broadcast — the whole packed meta
update of Algorithm 1 (v' = mu v + eta d; w~' = w~ + v'; w_j <- w~' for
every learner j) in a single Pallas pass (DESIGN.md §10).

After the packed block-momentum kernel (block_momentum.py) wrote w~',
``tree_broadcast_learners`` still re-read the full (rows, 128) meta plane
to materialize the (L, rows, 128) learner-dtype reset plane — one extra
whole-model HBM read per meta step that XLA cannot fuse away on TPU
because the momentum update is an opaque pallas_call. This kernel emits
the learner broadcast directly from the VMEM tile that just computed w~':

    block_momentum alone:  read w, v, a       write w', v'      (3R + 2W)
    + tree_broadcast:      read w'            write (L, ...)    (1R + LW)
    fused (this kernel):   read w, v, a       write w', v', (L, ...)
                                                                (3R + (2+L)W)

i.e. one full-plane read fewer per meta step, and the broadcast cast to
the learner compute dtype (bf16 on TPU: half-width writes) happens
in-register. The math is bit-identical to block_momentum_2d followed by
astype + broadcast — the jnp oracle in ref.py shares the exact op order,
so the packed/per-leaf dense parity stays bitwise (tests/test_pack.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_ROWS = 256
LANES = 128


def _kernel(w_ref, v_ref, a_ref, mu_ref, eta_ref, w_out, v_out, l_out, *,
            nesterov: bool):
    mu = mu_ref[0, 0]
    eta = eta_ref[0, 0]
    w = w_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    a = a_ref[...].astype(jnp.float32)
    d = a - w
    v_new = mu * v + eta * d
    if nesterov:
        w_new = w + mu * v_new + eta * d
    else:
        w_new = w + v_new
    w_out[...] = w_new.astype(w_out.dtype)
    v_out[...] = v_new.astype(v_out.dtype)
    # the learner reset: every learner's plane gets the cast copy of w~'
    # straight from VMEM — w~' is never re-read from HBM
    l_out[...] = jnp.broadcast_to(
        w_new.astype(l_out.dtype)[None], l_out.shape
    )


def fused_momentum_broadcast_2d(w, v, a, mu, eta, num_learners: int,
                                ldtype, *, nesterov: bool = False,
                                interpret: bool = False,
                                block: int | None = None):
    """w, v, a: (rows, 128) with rows % 8 == 0.

    Returns (w', v', learners) with learners an (L, rows, 128) ``ldtype``
    plane — every learner reset to the new meta params.

    w' and v' are written over w and v (``input_output_aliases``): each
    grid step reads its tile before writing the same tile, so when the
    caller donates the meta state (``MAvgConfig.donate``) the update runs
    in place. Without the alias XLA keeps the donated inputs alive beside
    the outputs: two extra (rows, 128) f32 planes, 3.7 GB at xlstm-350m
    width, enough to push its one-chip step past 16 GB of HBM.
    """
    rows, lanes = w.shape
    assert lanes == LANES and rows % 8 == 0, w.shape
    assert v.shape == w.shape and a.shape == w.shape, (v.shape, a.shape)
    L = int(num_learners)
    if block is None:
        block = min(BLOCK_ROWS, rows)
        while rows % block:
            block //= 2
    assert rows % block == 0, (rows, block)
    grid = (rows // block,)
    spec = pl.BlockSpec((block, LANES), lambda i: (i, 0))
    l_spec = pl.BlockSpec((L, block, LANES), lambda i: (0, i, 0))
    scalar_spec = pl.BlockSpec((1, 1), lambda i: (0, 0))
    mu_arr = jnp.asarray(mu, jnp.float32).reshape(1, 1)
    eta_arr = jnp.asarray(eta, jnp.float32).reshape(1, 1)
    return pl.pallas_call(
        functools.partial(_kernel, nesterov=nesterov),
        grid=grid,
        in_specs=[spec, spec, spec, scalar_spec, scalar_spec],
        out_specs=[spec, spec, l_spec],
        out_shape=[
            jax.ShapeDtypeStruct(w.shape, w.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
            jax.ShapeDtypeStruct((L,) + w.shape, jnp.dtype(ldtype)),
        ],
        input_output_aliases={0: 0, 1: 1},
        interpret=interpret,
    )(w, v, a, mu_arr, eta_arr)
