"""Flash attention (blocked online-softmax) as a Pallas TPU kernel.

TPU adaptation of the standard flash algorithm:
* grid = (batch*heads, q_blocks, kv_blocks); the kv dimension is the minor
  (sequential) grid axis, so the VMEM scratch accumulator persists across
  kv blocks of a fixed (bh, qi) pair — TPU grids are sequential loops, not
  CUDA thread blocks (DESIGN.md §4, hardware adaptation).
* BlockSpec index maps implement GQA natively: each query-head block pulls
  its kv block from head ``h // n_rep`` — no materialised repeat of K/V.
* Block shapes default to (128, head_dim) — sublane-aligned (8) and MXU-
  shaped; head_dim is padded to a lane multiple (128) by ops.py.
* Supports causal, sliding-window, and Hymba's globally-visible prefix
  (meta tokens), plus a kv-length mask for padded sequences.

Validated against ref.py (pure-jnp oracle) with interpret=True on CPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            scale, causal, window, prefix, kv_len, block_q, block_k):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32)  # (bq, d)
    k = k_ref[0].astype(jnp.float32)  # (bk, d)
    v = v_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # (bq, bk)

    qpos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = kpos < kv_len
    if causal:
        mask &= qpos >= kpos
    if window:
        win = qpos - kpos < window
        if prefix:
            win |= kpos < prefix
        mask &= win
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]  # (bq, 1)
    l_prev = l_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_new = l_prev * alpha + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(ki == nk - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(
            o_ref.dtype
        )


def flash_attention_bhsd(q, k, v, *, causal=True, sliding_window=0,
                         prefix_global=0, kv_len=None, scale=None,
                         block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                         interpret=False):
    """q: (BH, Sq, d); k, v: (BKV, Sk, d), BH = BKV * n_rep.

    Sq/Sk must be multiples of block_q/block_k; d should be lane-aligned
    (ops.py pads). kv_len masks padded key positions.
    """
    BH, Sq, d = q.shape
    BKV, Sk, _ = k.shape
    assert BH % BKV == 0, (BH, BKV)
    n_rep = BH // BKV
    kv_len = Sk if kv_len is None else kv_len
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    assert Sq % block_q == 0 and Sk % block_k == 0

    grid = (BH, Sq // block_q, Sk // block_k)
    kernel = functools.partial(
        _kernel,
        scale=scale if scale is not None else 1.0 / (d ** 0.5),
        causal=causal,
        window=sliding_window,
        prefix=prefix_global,
        kv_len=kv_len,
        block_q=block_q,
        block_k=block_k,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec(
                (1, block_k, d), lambda bh, qi, ki, n_rep=n_rep: (bh // n_rep, ki, 0)
            ),
            pl.BlockSpec(
                (1, block_k, d), lambda bh, qi, ki, n_rep=n_rep: (bh // n_rep, ki, 0)
            ),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, d), q.dtype),
        scratch_shapes=[
            _vmem((block_q, d), jnp.float32),
            _vmem((block_q, 1), jnp.float32),
            _vmem((block_q, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)


def _vmem(shape, dtype):
    return pltpu.VMEM(shape, dtype)
