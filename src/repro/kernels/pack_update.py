"""Fused packed-displacement update — displacement + error-feedback add +
stochastic-rounding quantize of the whole packed meta-plane in a single
HBM pass — as a Pallas TPU kernel (DESIGN.md §9).

On the per-leaf path the compressed meta average was three separate
pytree-wide passes per leaf (CompressedReducer.reduce + ops.quantize):

    delta = w_j - w~        read w, gp        write delta
    delta += e_j            read delta, e     write delta
    q, s = Q(delta); c = q*s; e' = delta - c   (quantize + dequantize +
                                                residual: 3 more passes)

Every pass is memory-bound with zero FLOP/byte reuse, so like
block_momentum.py the only lever is touching HBM once. This kernel
streams one (block, 128) VMEM tile of the learner plane per grid step and
emits the *dequantized* compressed displacement c = Q(w - w~ + e) and the
new EF residual e' = (w - w~ + e) - c in the same pass: 3-4 reads
(w, gp, u, optionally e) + 2-3 writes (c, scales, optionally e') of the
packed plane, and XLA cannot re-split it. gp is read once per learner
block via the BlockSpec index map — no (L, rows, 128) broadcast of the
meta params ever materializes in HBM.

Quantization semantics are identical to kernels/quantize.py: per-chunk
max-abs f32 scales over ``block`` rows x 128 lanes, unbiased stochastic
floor q = floor(x/s + u) with caller-supplied uniforms (shared with the
jnp oracle in ref.py, so the quantization decisions q are bit-identical
and c/err/scales agree to one scale ulp — see quantize.py for why the
dither is streamed in rather than drawn on-core). Chunks are
per-learner (the grid is (L, rows // block)), so every learner's
displacement is scaled independently of its peers, matching the wire
model where each learner ships its own payload.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_ROWS = 64  # scale-chunk rows, matching quantize.py's wire layout
LANES = 128
# one chunk's scale in the kernels' (L, nchunks, 1, 1) scales array: the
# TPU lowering takes a block whose last two dims are (8, 128) multiples or
# the array's own, and XLA stores that shape densely
SCALE_BLOCK = (1, 1, 1, 1)
EPS = 1e-12  # all-zero chunks (e.g. pure padding): finite scale, q = 0


def _kernel(w_ref, g_ref, *rest, qmax: int, has_residual: bool):
    if has_residual:
        e_ref, u_ref, c_ref, err_ref, s_ref = rest
    else:
        u_ref, c_ref, err_ref, s_ref = rest
    d = w_ref[...].astype(jnp.float32) - g_ref[...].astype(jnp.float32)[None]
    if has_residual:
        d = d + e_ref[...].astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(d)), EPS) / qmax
    s_ref[...] = jnp.broadcast_to(scale, s_ref.shape)
    q = jnp.clip(jnp.floor(d / scale + u_ref[...]), -qmax, qmax)
    c = q * scale
    c_ref[...] = c
    err_ref[...] = d - c


def pack_update_3d(w, g, e, u, *, qmax: int = 127, block: int | None = None,
                   interpret: bool = False):
    """w: (L, rows, 128) learner plane (any float dtype); g: (rows, 128)
    meta params; e: (L, rows, 128) f32 EF residual or None; u: (L, rows,
    128) U[0,1) dither.

    Returns (c, err, scales):
      c       (L, rows, 128) f32 — dequantized compressed displacement
              Q(w - g [+ e]), what crosses the wire
      err     (L, rows, 128) f32 — quantization error (the next EF
              residual when error feedback is on; the comm_error_norm
              metric either way)
      scales  (L, rows // block) f32 — per-chunk wire scales
    """
    L, rows, lanes = w.shape
    assert lanes == LANES and rows % 8 == 0, w.shape
    assert g.shape == (rows, LANES), (g.shape, w.shape)
    b = min(BLOCK_ROWS if block is None else block, rows)
    # callers resolve the chunk height via quantize.choose_block (see
    # ops.pack_update); failing loudly here keeps the kernel and the
    # jnp oracle on identical chunk geometry instead of silently
    # shrinking the block on one side only
    assert rows % b == 0, (rows, b)
    grid = (L, rows // b)
    spec = pl.BlockSpec((1, b, LANES), lambda l, i: (l, i, 0))
    g_spec = pl.BlockSpec((b, LANES), lambda l, i: (i, 0))
    s_spec = pl.BlockSpec(SCALE_BLOCK, lambda l, i: (l, i, 0, 0))
    in_specs = [spec, g_spec] + ([spec] if e is not None else []) + [spec]
    args = (w, g) + ((e,) if e is not None else ()) + (u,)
    c, err, scales = pl.pallas_call(
        functools.partial(_kernel, qmax=qmax, has_residual=e is not None),
        grid=grid,
        in_specs=in_specs,
        out_specs=[spec, spec, s_spec],
        out_shape=[
            jax.ShapeDtypeStruct(w.shape, jnp.float32),
            jax.ShapeDtypeStruct(w.shape, jnp.float32),
            jax.ShapeDtypeStruct((L, rows // b, 1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(*args)
    return c, err, scales.reshape(L, rows // b)


# ---------------------------------------------------------------------------
# compress-only variant (the gossip / masked-hierarchical-inner path)
# ---------------------------------------------------------------------------


def _compress_kernel(d_ref, u_ref, *out, qmax: int, with_err: bool):
    if with_err:
        c_ref, err_ref, s_ref = out
    else:
        c_ref, s_ref = out
    d = d_ref[...].astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(d)), EPS) / qmax
    s_ref[...] = jnp.broadcast_to(scale, s_ref.shape)
    q = jnp.clip(jnp.floor(d / scale + u_ref[...]), -qmax, qmax)
    c = q * scale
    c_ref[...] = c
    if with_err:
        err_ref[...] = d - c


def pack_compress_3d(d, u, *, qmax: int = 127, block: int | None = None,
                     with_err: bool = True, interpret: bool = False):
    """Quantize an already-formed (L, rows, 128) displacement plane.

    The compress-stage routes (gossip neighbor exchange, the masked
    hierarchical inner average — topology.gossip.compress_stack) hand the
    reducer a displacement delta_j = w_j - x_j they computed themselves;
    running those through pack_update_3d meant synthesizing a zero gp
    plane just so the kernel could subtract it — one full-plane HBM read
    of zeros per mix. This variant reads (d, u) and writes (c, scales)
    plus, under ``with_err``, the EF residual err = d - c the same pass
    already computed: 2R + 3W (error feedback, which keeps err as the
    next residual) or 2R + 2W (no EF — an output of an opaque
    pallas_call cannot be DCE'd by XLA, so the err plane must not exist
    at all when nobody reads it) instead of pack_update's 3R + 3W.

    Bitwise-identical to ``pack_update_3d(d, zeros, None, u)`` (d - 0 is
    exact), same chunk geometry and dither contract — so the fused-reduce
    vs compress-only consistency invariants (DESIGN.md §9) survive, now
    pinned in tests/test_zero_copy.py. Returns (c, err, scales) with
    err=None when ``with_err`` is off.
    """
    L, rows, lanes = d.shape
    assert lanes == LANES and rows % 8 == 0, d.shape
    b = min(BLOCK_ROWS if block is None else block, rows)
    assert rows % b == 0, (rows, b)
    grid = (L, rows // b)
    spec = pl.BlockSpec((1, b, LANES), lambda l, i: (l, i, 0))
    s_spec = pl.BlockSpec(SCALE_BLOCK, lambda l, i: (l, i, 0, 0))
    plane = jax.ShapeDtypeStruct(d.shape, jnp.float32)
    scales = jax.ShapeDtypeStruct((L, rows // b, 1, 1), jnp.float32)
    out = pl.pallas_call(
        functools.partial(_compress_kernel, qmax=qmax, with_err=with_err),
        grid=grid,
        in_specs=[spec, spec],
        out_specs=[spec, spec, s_spec] if with_err else [spec, s_spec],
        out_shape=[plane, plane, scales] if with_err else [plane, scales],
        interpret=interpret,
    )(d, u)
    c, err, s = out if with_err else (out[0], None, out[1])
    return c, err, s.reshape(L, rows // b)
