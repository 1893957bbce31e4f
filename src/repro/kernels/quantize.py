"""Stochastic-rounding quantize / dequantize of meta displacements as
Pallas TPU kernels — the compression hot path of ``repro.comm``.

The meta all-reduce ships each learner's displacement w_j - w~ (comm is
the paper's entire cost model); quantizing it to int8/int4 with per-chunk
f32 scales cuts bytes-on-wire ~4x/8x. Like block_momentum.py the op is
purely memory-bound, so each kernel streams one (chunk_rows, 128) VMEM
tile per grid step and touches HBM once: the quantizer reads (x, u) and
writes (q, scale); the dequantizer reads (q, scale) and writes f32.

Rounding is the unbiased stochastic floor  q = floor(x/s + u),
u ~ U[0, 1), so E[q * s] = x and the error-feedback residual (DESIGN.md
§5) has zero-mean noise. The uniforms are generated by the caller with
``jax.random`` and streamed in rather than drawn on-core with
``pltpu.prng_random_bits``: the TPU PRNG primitives have no interpret-mode
lowering on CPU (where all tests run), and sharing one `u` between the
kernel and the jnp oracle makes the parity tests exact instead of
statistical.

Layout: callers flatten each leaf to (rows, 128) with rows % 8 == 0
(ops.py pads); the grid walks chunks of ``block`` rows and chunk i owns
scale[i] = max|x_chunk| / qmax — the issue's "per-chunk f32 scales".
Inside the kernels the scales are an (nchunks, 1, 1) array in (1, 1, 1)
blocks: the TPU lowering takes a block whose last two dims are (8, 128)
multiples or the array's own, and XLA stores that shape densely.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_ROWS = 64  # 64 x 128 values per scale chunk (3 B overhead per KiB)
LANES = 128
EPS = 1e-12  # all-zero chunks: keep the scale finite, q stays 0


def choose_block(rows: int, block: int | None = None) -> int:
    """Largest power-of-two-reduced chunk height that divides ``rows``."""
    b = min(BLOCK_ROWS if block is None else block, rows)
    while rows % b:
        b //= 2
    return b


SCALE_BLOCK = (1, 1, 1)  # one chunk's scale in the (nchunks, 1, 1) array


def _quant_kernel(x_ref, u_ref, q_ref, s_ref, *, qmax: int):
    x = x_ref[...].astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), EPS) / qmax
    s_ref[...] = jnp.broadcast_to(scale, s_ref.shape)
    q = jnp.floor(x / scale + u_ref[...])
    q_ref[...] = jnp.clip(q, -qmax, qmax).astype(q_ref.dtype)


def _dequant_kernel(q_ref, s_ref, out_ref):
    out_ref[...] = q_ref[...].astype(jnp.float32) * s_ref[0]


def quantize_2d(x, u, *, qmax: int = 127, block: int | None = None,
                interpret: bool = False):
    """x, u: (rows, 128) f32, rows % 8 == 0; u ~ U[0, 1) stochastic dither.

    Returns (q int8 (rows, 128), scales f32 (rows // block, 1)).
    """
    rows, lanes = x.shape
    assert lanes == LANES and rows % 8 == 0, x.shape
    b = choose_block(rows, block)
    grid = (rows // b,)
    spec = pl.BlockSpec((b, LANES), lambda i: (i, 0))
    scale_spec = pl.BlockSpec(SCALE_BLOCK, lambda i: (i, 0, 0))
    q, scales = pl.pallas_call(
        functools.partial(_quant_kernel, qmax=qmax),
        grid=grid,
        in_specs=[spec, spec],
        out_specs=[spec, scale_spec],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, jnp.int8),
            jax.ShapeDtypeStruct((rows // b, 1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(x, u)
    return q, scales.reshape(rows // b, 1)


def dequantize_2d(q, scales, *, block: int | None = None,
                  interpret: bool = False):
    """Inverse of quantize_2d: (rows, 128) int8 + per-chunk scales -> f32."""
    rows, lanes = q.shape
    assert lanes == LANES and rows % 8 == 0, q.shape
    nchunks = scales.shape[0]
    assert rows % nchunks == 0, (rows, scales.shape)
    b = rows // nchunks
    del block  # implied by the scales shape
    grid = (rows // b,)
    spec = pl.BlockSpec((b, LANES), lambda i: (i, 0))
    scale_spec = pl.BlockSpec(SCALE_BLOCK, lambda i: (i, 0, 0))
    return pl.pallas_call(
        _dequant_kernel,
        grid=grid,
        in_specs=[spec, scale_spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, jnp.float32),
        interpret=interpret,
    )(q, scales.reshape(nchunks, 1, 1))
