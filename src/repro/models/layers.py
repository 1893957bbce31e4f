"""Shared neural-net building blocks (pure functional JAX).

Conventions
-----------
* Params are nested dicts of jnp arrays, stored in float32; forward passes
  cast to ``cfg.dtype`` (bf16 on TPU) and produce float32 logits.
* Attention projections are kept 3-D ``(d_model, heads, head_dim)`` so the
  sharding rules (repro/sharding) can put the tensor-parallel axis on the
  heads dim when divisible and fall back to the d_model dim otherwise
  (e.g. qwen2-7b's 28 heads on a 16-way model axis).
* Layer stacks are scanned (``lax.scan`` over a leading layer axis) to keep
  HLO size and compile time bounded for 126-layer configs.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig

# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------


def _dense_init(key, shape, in_axis_size):
    scale = 1.0 / math.sqrt(max(1, in_axis_size))
    return jax.random.normal(key, shape, dtype=jnp.float32) * scale


def init_attention(key, cfg: ModelConfig) -> dict:
    d, nq, nkv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": _dense_init(ks[0], (d, nq, hd), d),
        "wk": _dense_init(ks[1], (d, nkv, hd), d),
        "wv": _dense_init(ks[2], (d, nkv, hd), d),
        "wo": _dense_init(ks[3], (nq, hd, d), nq * hd),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((nq, hd), jnp.float32)
        p["bk"] = jnp.zeros((nkv, hd), jnp.float32)
        p["bv"] = jnp.zeros((nkv, hd), jnp.float32)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), jnp.float32)
        p["k_norm"] = jnp.ones((hd,), jnp.float32)
    return p


def init_mlp(key, d_model: int, d_ff: int) -> dict:
    k1, k2 = jax.random.split(key)
    return {
        "wi": _dense_init(k1, (d_model, 2, d_ff), d_model),  # [gate, up]
        "wo": _dense_init(k2, (d_ff, d_model), d_ff),
    }


def init_rmsnorm(d: int) -> dict:
    return {"scale": jnp.ones((d,), jnp.float32)}


# ---------------------------------------------------------------------------
# core ops
# ---------------------------------------------------------------------------


def rmsnorm(x, p, eps: float = 1e-5):
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    out = x32 * lax.rsqrt(var + eps) * p["scale"]
    return out.astype(dt)


def _rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x, positions, theta: float):
    """x: (..., S, H, D); positions: broadcastable to (..., S)."""
    d = x.shape[-1]
    freqs = _rope_freqs(d, theta)  # (D/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, D/2)
    cos = jnp.cos(angles)[..., None, :]  # (..., S, 1, D/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def swiglu(x, p):
    h = jnp.einsum("...d,dtf->...tf", x, p["wi"].astype(x.dtype))
    gate, up = h[..., 0, :], h[..., 1, :]
    return jnp.einsum(
        "...f,fd->...d", jax.nn.silu(gate) * up, p["wo"].astype(x.dtype)
    )


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------


def _qkv(x, p, cfg: ModelConfig, positions):
    dt = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(dt))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(dt)
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    if cfg.qk_norm:
        q = rmsnorm(q, {"scale": p["q_norm"]}, cfg.norm_eps)
        k = rmsnorm(k, {"scale": p["k_norm"]}, cfg.norm_eps)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def attention_scores_block(q, k, v, scale, mask):
    """Plain attention on one (q-block, kv-block) pair; f32 softmax."""
    s = jnp.einsum("bqhk,bshk->bhqs", q, k).astype(jnp.float32) * scale
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqs,bshk->bqhk", p, v)


def _expand_kv(k, n_rep: int):
    if n_rep == 1:
        return k
    return jnp.repeat(k, n_rep, axis=2)


def full_attention(q, k, v, *, causal, sliding_window=0, q_offset=0,
                   prefix_global=0):
    """Reference attention (materialises the score matrix). Use for S<=4k."""
    B, Sq, nq, hd = q.shape
    Sk = k.shape[1]
    n_rep = nq // k.shape[2]
    k, v = _expand_kv(k, n_rep), _expand_kv(v, n_rep)
    qpos = jnp.arange(Sq) + q_offset
    kpos = jnp.arange(Sk)
    mask = jnp.ones((Sq, Sk), bool)
    if causal:
        mask &= qpos[:, None] >= kpos[None, :]
    if sliding_window:
        win = qpos[:, None] - kpos[None, :] < sliding_window
        if prefix_global:  # meta/global prefix tokens always attendable
            win |= kpos[None, :] < prefix_global
        mask &= win
    return attention_scores_block(q, k, v, 1.0 / math.sqrt(hd), mask[None, None])


def chunked_attention(
    q, k, v, *, causal, sliding_window=0, q_chunk=512, kv_chunk=1024,
    prefix_global=0,
):
    """Blockwise online-softmax attention in pure jnp (flash-style).

    This is the XLA path used for long sequences (and the oracle the Pallas
    kernel is validated against lives in kernels/flash_attention/ref.py and
    simply calls this). Memory is O(q_chunk * kv_chunk) per block instead of
    O(S^2).
    """
    B, S, nq, hd = q.shape
    n_rep = nq // k.shape[2]
    scale = 1.0 / math.sqrt(hd)
    # largest chunk dividing S (prefix tokens can make S non-power-of-two,
    # e.g. 32768 text + 256 patches = 33024 -> chunk 256)
    q_chunk = math.gcd(min(q_chunk, S), S)
    kv_chunk = math.gcd(min(kv_chunk, S), S)
    nq_blocks, nkv_blocks = S // q_chunk, S // kv_chunk

    qb = q.reshape(B, nq_blocks, q_chunk, nq, hd)
    kb = k.reshape(B, nkv_blocks, kv_chunk, k.shape[2], hd)
    vb = v.reshape(B, nkv_blocks, kv_chunk, v.shape[2], hd)

    def q_block(qi, q_i):
        # online softmax over kv blocks
        acc0 = jnp.zeros((B, q_chunk, nq, hd), jnp.float32)
        m0 = jnp.full((B, nq, q_chunk), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((B, nq, q_chunk), jnp.float32)

        def kv_step(carry, inp):
            acc, m, l = carry
            ki, k_j, v_j = inp
            k_j = _expand_kv(k_j, n_rep)
            v_j = _expand_kv(v_j, n_rep)
            s = jnp.einsum("bqhk,bshk->bhqs", q_i, k_j).astype(jnp.float32)
            s = s * scale
            qpos = qi * q_chunk + jnp.arange(q_chunk)
            kpos = ki * kv_chunk + jnp.arange(kv_chunk)
            mask = jnp.ones((q_chunk, kv_chunk), bool)
            if causal:
                mask &= qpos[:, None] >= kpos[None, :]
            if sliding_window:
                win = qpos[:, None] - kpos[None, :] < sliding_window
                if prefix_global:
                    win |= kpos[None, :] < prefix_global
                mask &= win
            s = jnp.where(mask[None, None], s, -1e30)
            m_new = jnp.maximum(m, s.max(-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            l_new = l * alpha + p.sum(-1)
            pv = jnp.einsum("bhqs,bshk->bqhk", p.astype(q_i.dtype), v_j)
            acc_new = acc * alpha.transpose(0, 2, 1)[..., None] + pv
            return (acc_new, m_new, l_new), None

        ks = jnp.arange(nkv_blocks)
        (acc, m, l), _ = lax.scan(
            kv_step, (acc0, m0, l0), (ks, kb.swapaxes(0, 1), vb.swapaxes(0, 1))
        )
        out = acc / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
        return out.astype(q.dtype)

    outs = lax.map(lambda args: q_block(*args), (jnp.arange(nq_blocks),
                                                 qb.swapaxes(0, 1)))
    # outs: (nq_blocks, B, q_chunk, nq, hd)
    return outs.transpose(1, 0, 2, 3, 4).reshape(B, S, nq, hd)


# Sequences above this use blockwise online-softmax attention in jnp
# (never materialising the S x S score tensor at once). Lowering this to
# 2048 for train_4k was tried and REFUTED: the unfused jnp online-softmax
# touches each score block ~6x (XLA writes every intermediate), 2.5x more
# HBM traffic than the one-shot S^2 softmax. The true fix on TPU is the
# Pallas flash kernel (ops.flash_attention): one VMEM pass, HBM traffic =
# q+k+v+o.
# (env override kept for reproducing that measurement)
import os as _os

ATTN_CHUNK_THRESHOLD = int(_os.environ.get("REPRO_ATTN_CHUNK_THRESHOLD", 8192))


def attention_block_kv(x, p, cfg: ModelConfig, positions, use_pallas=False):
    """Self-attention over a full sequence; also returns (k, v) for
    prefill cache construction."""
    q, k, v = _qkv(x, p, cfg, positions)
    S = x.shape[1]
    if use_pallas:
        from repro.kernels import ops as kops

        out = kops.flash_attention(
            q, k, v, causal=cfg.causal, sliding_window=cfg.sliding_window
        )
    elif S > ATTN_CHUNK_THRESHOLD:
        out = chunked_attention(
            q, k, v, causal=cfg.causal, sliding_window=cfg.sliding_window
        )
    else:
        out = full_attention(
            q, k, v, causal=cfg.causal, sliding_window=cfg.sliding_window
        )
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype)), k, v


def attention_block(x, p, cfg: ModelConfig, positions, use_pallas=False):
    """Self-attention over a full sequence (train / prefill)."""
    out, _, _ = attention_block_kv(x, p, cfg, positions, use_pallas)
    return out


def attention_decode(x, p, cfg: ModelConfig, k_cache, v_cache, pos):
    """One-token decode against a KV cache.

    x: (B, 1, d); k_cache/v_cache: (B, S, nkv, hd); pos: () current index.
    Returns (out (B,1,d), new_k_cache, new_v_cache).
    """
    q, k_new, v_new = _qkv(x, p, cfg, pos[None] if pos.ndim == 0 else pos)
    B = x.shape[0]
    k_cache = lax.dynamic_update_slice_in_dim(
        k_cache, k_new.astype(k_cache.dtype), pos, axis=1
    )
    v_cache = lax.dynamic_update_slice_in_dim(
        v_cache, v_new.astype(v_cache.dtype), pos, axis=1
    )
    S = k_cache.shape[1]
    nq, hd = cfg.num_heads, cfg.head_dim
    n_rep = nq // cfg.num_kv_heads
    kk = _expand_kv(k_cache.astype(q.dtype), n_rep)
    vv = _expand_kv(v_cache.astype(q.dtype), n_rep)
    s = jnp.einsum("bqhk,bshk->bhqs", q, kk).astype(jnp.float32)
    s = s / math.sqrt(hd)
    kpos = jnp.arange(S)
    valid = kpos <= pos
    if cfg.sliding_window:
        valid &= kpos > pos - cfg.sliding_window
    s = jnp.where(valid[None, None, None, :], s, -1e30)
    prob = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhqs,bshk->bqhk", prob, vv)
    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
    return out, k_cache, v_cache


# ---------------------------------------------------------------------------
# embeddings / head
# ---------------------------------------------------------------------------


def init_embed(key, cfg: ModelConfig) -> dict:
    k1, k2 = jax.random.split(key)
    p = {"embedding": jax.random.normal(k1, (cfg.vocab_size, cfg.d_model)) * 0.02}
    if not cfg.tie_embeddings:
        p["head"] = _dense_init(k2, (cfg.d_model, cfg.vocab_size), cfg.d_model)
    if cfg.meta_tokens:
        p["meta"] = jax.random.normal(
            jax.random.fold_in(key, 7), (cfg.meta_tokens, cfg.d_model)
        ) * 0.02
    if cfg.input_mode == "tokens+patches":
        # projector stub is identity-shaped; learnable patch positional bias
        p["patch_pos"] = jnp.zeros((cfg.num_patches, cfg.d_model), jnp.float32)
    return p


# the work over the vocabulary runs under the scope "obs.head", so a
# profile tells it from the blocks'
@jax.named_scope("obs.head")
def embed_tokens(p, cfg: ModelConfig, tokens):
    return p["embedding"].astype(jnp.dtype(cfg.dtype))[tokens]


@jax.named_scope("obs.head")
def lm_head(p, cfg: ModelConfig, x):
    if cfg.tie_embeddings:
        w = p["embedding"].T
    else:
        w = p["head"]
    return jnp.einsum("bsd,dv->bsv", x, w.astype(x.dtype)).astype(jnp.float32)


@jax.named_scope("obs.head")
def cross_entropy(logits, labels, mask=None):
    """Mean token cross-entropy. labels: int32, -1 entries ignored."""
    valid = labels >= 0
    if mask is not None:
        valid &= mask
    labels_c = jnp.maximum(labels, 0)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, labels_c[..., None], axis=-1)[..., 0]
    nll = jnp.where(valid, nll, 0.0)
    return nll.sum() / jnp.maximum(valid.sum(), 1)
