"""xLSTM (arXiv:2405.04517): mLSTM (matrix-memory) + sLSTM (scalar-memory)
blocks with exponential gating, alternating in a ``slstm_every`` pattern.

The mLSTM runs in one of two equivalent forms, chosen from the sequence
length: the chunkwise-parallel form when the length divides by
``MLSTM_CHUNK`` (training and long prefills), the step-recurrent
``lax.scan`` over time otherwise (decode, short or ragged lengths). Decode
carries the per-layer recurrent state, so serving one token is O(1) in
context length.

State pytrees:
  mLSTM: C (B, nh, hd, hd) matrix memory, n (B, nh, hd), m (B, nh)
  sLSTM: c, n, h (B, nh, hd), m (B, nh, hd)
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.kernels import ops as kops
from repro.models import layers as L
from repro.models.layers import _dense_init

# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _init_mlstm_block(key, cfg: ModelConfig) -> dict:
    d = cfg.d_model
    d_in = cfg.ssm_expand * d
    nh = cfg.num_heads
    hd = d_in // nh
    ks = jax.random.split(key, 8)
    return {
        "norm": L.init_rmsnorm(d),
        "w_up": _dense_init(ks[0], (d, 2, d_in), d),  # [x-path, z-gate]
        "conv": _dense_init(ks[1], (cfg.ssm_conv, d_in), cfg.ssm_conv),
        "wq": _dense_init(ks[2], (d_in, nh, hd), d_in),
        "wk": _dense_init(ks[3], (d_in, nh, hd), d_in),
        "wv": _dense_init(ks[4], (d_in, nh, hd), d_in),
        "w_i": _dense_init(ks[5], (d_in, nh), d_in),
        "b_i": jnp.zeros((nh,), jnp.float32),
        "w_f": _dense_init(ks[6], (d_in, nh), d_in),
        "b_f": jnp.ones((nh,), jnp.float32) * 3.0,  # forget-bias init
        "out_norm": L.init_rmsnorm(d_in),
        "w_down": _dense_init(ks[7], (d_in, d), d_in),
    }


def _init_slstm_block(key, cfg: ModelConfig) -> dict:
    d = cfg.d_model
    nh = cfg.num_heads
    hd = d // nh
    ff = -(-int(d * 4 / 3) // 128) * 128  # proj factor 4/3 rounded to 128
    ks = jax.random.split(key, 11)
    p = {"norm": L.init_rmsnorm(d), "out_norm": L.init_rmsnorm(d)}
    for gi, g in enumerate(("i", "f", "z", "o")):
        p[f"w_{g}"] = _dense_init(ks[gi], (d, nh, hd), d)
        p[f"r_{g}"] = _dense_init(ks[4 + gi], (nh, hd, hd), hd)
        p[f"b_{g}"] = (jnp.ones((nh, hd)) * 3.0 if g == "f" else jnp.zeros((nh, hd)))
    p["w_up"] = _dense_init(ks[8], (d, 2, ff), d)
    p["w_down"] = _dense_init(ks[9], (ff, d), ff)
    return p


def init(rng, cfg: ModelConfig) -> dict:
    assert cfg.slstm_every >= 2 and cfg.num_layers % cfg.slstm_every == 0
    G = cfg.num_layers // cfg.slstm_every  # super-blocks
    M = cfg.slstm_every - 1  # mLSTM blocks per super-block
    k_e, k_m, k_s = jax.random.split(rng, 3)
    km = jax.random.split(k_m, G * M).reshape(G, M, 2)
    params = {
        "embed": L.init_embed(k_e, cfg),
        "final_norm": L.init_rmsnorm(cfg.d_model),
        "mlstm": jax.vmap(jax.vmap(partial(_init_mlstm_block, cfg=cfg)))(km),
        "slstm": jax.vmap(partial(_init_slstm_block, cfg=cfg))(
            jax.random.split(k_s, G)
        ),
    }
    return params


# ---------------------------------------------------------------------------
# cells
# ---------------------------------------------------------------------------


def _causal_conv(x, w):
    """x: (B, S, d_in); w: (k, d_in) depthwise causal conv."""
    k = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    out = jnp.zeros_like(x)
    for i in range(k):
        out = out + xp[:, i : i + x.shape[1]] * w[i].astype(x.dtype)
    return out


# Chunk length of the chunkwise-parallel mLSTM form, used for every
# sequence whose length divides by it (0 = always the recurrent form). The
# recurrent form keeps the (B, nh, hd, hd) matrix memory of EVERY token for
# the backward pass — at xlstm-350m width (4, 4, 512, 512) f32 per token,
# 48 GiB for one L=2, S=512 meta step, which no 16 GiB chip holds; the
# chunkwise form (equivalent math, xLSTM paper appendix) keeps it once per
# CHUNK and turns the intra-chunk work into MXU-shaped matmuls.
MLSTM_CHUNK = 64


def _mlstm_inputs(bp, cfg: ModelConfig, x, state):
    """Shared projections for both mLSTM integrators."""
    B, S, d = x.shape
    d_in = cfg.ssm_expand * d
    nh = cfg.num_heads
    hd = d_in // nh
    dt = x.dtype
    C0, n0, m0, conv_buf = state

    xn = L.rmsnorm(x, bp["norm"], cfg.norm_eps)
    up = jnp.einsum("bsd,dtf->bstf", xn, bp["w_up"].astype(dt))
    xu, z = up[..., 0, :], up[..., 1, :]
    # carry the causal-conv receptive field across calls (decode needs the
    # last ssm_conv-1 inputs; zeros at t=0 match the train-time zero pad)
    kc = cfg.ssm_conv - 1
    conv_in = jnp.concatenate([conv_buf.astype(xu.dtype), xu], axis=1)
    xc = jax.nn.silu(_causal_conv(conv_in, bp["conv"]))[:, kc:]
    new_conv_buf = conv_in[:, -kc:].astype(jnp.float32)
    q = jnp.einsum("bsf,fhk->bshk", xc, bp["wq"].astype(dt))
    k = jnp.einsum("bsf,fhk->bshk", xc, bp["wk"].astype(dt)) / jnp.sqrt(
        jnp.float32(hd)
    ).astype(dt)
    v = jnp.einsum("bsf,fhk->bshk", xu, bp["wv"].astype(dt))
    i_pre = (
        jnp.einsum("bsf,fh->bsh", xc, bp["w_i"].astype(dt)).astype(jnp.float32)
        + bp["b_i"]
    )
    f_pre = (
        jnp.einsum("bsf,fh->bsh", xc, bp["w_f"].astype(dt)).astype(jnp.float32)
        + bp["b_f"]
    )
    return q, k, v, i_pre, f_pre, z, new_conv_buf, (C0, n0, m0)


def mlstm_chunked(bp, cfg: ModelConfig, x, state=None, chunk: int = 64):
    """Chunkwise-parallel mLSTM (math identical to the recurrence).

    Within a chunk of length T, with b_t = cumsum(f_pre) and stabiliser
    m_t = max(b_t + m0, max_{s<=t}(b_t - b_s + i_s)):
        h_t = [ sum_{s<=t} e^{b_t-b_s+i_s-m_t} (q_t.k_s) v_s
                + e^{b_t+m0-m_t} C0 q_t ] / max(|q_t . n_t|, 1)
    and the chunk-final (C, n, m) feeds the next chunk — one HBM round
    trip of the matrix memory per chunk instead of per token.
    """
    B, S, d = x.shape
    if state is None:
        state = mlstm_init_state(cfg, B)
    q, k, v, i_pre, f_pre, z, new_conv_buf, (C0, n0, m0) = _mlstm_inputs(
        bp, cfg, x, state
    )
    dt = x.dtype
    nh = cfg.num_heads
    d_in = cfg.ssm_expand * d
    assert S % chunk == 0, (S, chunk)
    NC, T = S // chunk, chunk

    def resh(a):  # (B, S, nh, hd) -> (NC, B, nh, T, hd) f32
        return (
            a.astype(jnp.float32)
            .reshape(B, NC, T, nh, -1)
            .transpose(1, 0, 3, 2, 4)
        )

    qs, ks, vs = resh(q), resh(k), resh(v)
    gates = lambda g: g.reshape(B, NC, T, nh).transpose(1, 0, 3, 2)  # (NC,B,nh,T)
    iis, ffs = gates(i_pre), gates(f_pre)
    tril = jnp.tril(jnp.ones((T, T), bool))

    def one_chunk(carry, inp):
        C, n, m = carry
        qc, kc_, vc, ic, fc = inp  # (B,nh,T,hd) / (B,nh,T)
        b = jnp.cumsum(fc, axis=-1)  # (B,nh,T)
        # running stabiliser: m_t = max(b_t + m0, b_t + cummax(i_s - b_s))
        running = jax.lax.cummax(ic - b, axis=ic.ndim - 1)
        m_t = jnp.maximum(b + m[..., None], b + running)  # (B,nh,T)
        inter = jnp.exp(b + m[..., None] - m_t)  # (B,nh,T)
        # decay matrix D_ts = exp(b_t - b_s + i_s - m_t), s <= t
        logD = b[..., :, None] - b[..., None, :] + ic[..., None, :] \
            - m_t[..., :, None]
        D = jnp.where(tril, jnp.exp(logD), 0.0)  # (B,nh,T,T)
        scores = jnp.einsum("bhtk,bhsk->bhts", qc, kc_) * D
        num = jnp.einsum("bhts,bhsv->bhtv", scores, vc)
        num = num + inter[..., None] * jnp.einsum("bhtk,bhvk->bhtv", qc, C)
        n_t = jnp.einsum("bhts,bhsk->bhtk", D, kc_) + inter[..., None] * n[
            ..., None, :
        ]
        den = jnp.maximum(
            jnp.abs(jnp.einsum("bhtk,bhtk->bht", qc, n_t)), 1.0
        )
        h = num / den[..., None]  # (B,nh,T,hd)
        # chunk-final state (t = T-1 weights, same stabiliser convention)
        m_end = m_t[..., -1]
        w_s = jnp.exp(b[..., -1:] - b + ic - m_end[..., None])  # (B,nh,T)
        C_new = jnp.exp(b[..., -1] + m - m_end)[..., None, None] * C \
            + jnp.einsum("bhsv,bhsk->bhvk", vc * w_s[..., None], kc_)
        n_new = jnp.exp(b[..., -1] + m - m_end)[..., None] * n \
            + jnp.einsum("bhs,bhsk->bhk", w_s, kc_)
        return (C_new, n_new, m_end), h

    (C, n, m), hs = lax.scan(one_chunk, (C0, n0, m0), (qs, ks, vs, iis, ffs))
    # hs: (NC, B, nh, T, hd) -> (B, S, d_in)
    h = hs.transpose(1, 0, 3, 2, 4).reshape(B, S, d_in).astype(dt)
    h = L.rmsnorm(h, bp["out_norm"], cfg.norm_eps) * jax.nn.silu(z)
    out = jnp.einsum("bsf,fd->bsd", h, bp["w_down"].astype(dt))
    return x + out, (C, n, m, new_conv_buf)


def mlstm_seq(bp, cfg: ModelConfig, x, state=None):
    """x: (B, S, d). Returns (out (B, S, d), final state).

    The chunkwise form when S divides by ``MLSTM_CHUNK``, else the
    step-recurrent form."""
    S = x.shape[1]
    if MLSTM_CHUNK and S % MLSTM_CHUNK == 0:
        return mlstm_chunked(bp, cfg, x, state, chunk=MLSTM_CHUNK)
    return mlstm_recurrent(bp, cfg, x, state)


def mlstm_recurrent(bp, cfg: ModelConfig, x, state=None):
    """The step-recurrent mLSTM: one ``lax.scan`` step per token."""
    B, S, d = x.shape
    d_in = cfg.ssm_expand * d
    nh = cfg.num_heads
    hd = d_in // nh
    dt = x.dtype

    if state is None:
        state = mlstm_init_state(cfg, B)
    q, k, v, i_pre, f_pre, z, new_conv_buf, (C0, n0, m0) = _mlstm_inputs(
        bp, cfg, x, state
    )

    def step(carry, inp):
        C, n, m = carry
        qt, kt, vt, it, ft = inp  # (B,nh,hd)...(B,nh)
        m_new = jnp.maximum(ft + m, it)
        i_g = jnp.exp(it - m_new)[..., None]
        f_g = jnp.exp(ft + m - m_new)[..., None]
        kt32, vt32, qt32 = (a.astype(jnp.float32) for a in (kt, vt, qt))
        C = f_g[..., None] * C + i_g[..., None] * (
            vt32[..., :, None] * kt32[..., None, :]
        )
        n = f_g * n + i_g * kt32
        num = jnp.einsum("bhvk,bhk->bhv", C, qt32)
        den = jnp.maximum(
            jnp.abs(jnp.einsum("bhk,bhk->bh", n, qt32))[..., None], 1.0
        )
        h = (num / den).astype(dt)
        return (C, n, m_new), h

    xs = (
        q.transpose(1, 0, 2, 3),
        k.transpose(1, 0, 2, 3),
        v.transpose(1, 0, 2, 3),
        i_pre.transpose(1, 0, 2),
        f_pre.transpose(1, 0, 2),
    )
    (C, n, m), hs = lax.scan(step, (C0, n0, m0), xs)
    h = hs.transpose(1, 0, 2, 3).reshape(B, S, d_in)
    h = L.rmsnorm(h, bp["out_norm"], cfg.norm_eps) * jax.nn.silu(z)
    out = jnp.einsum("bsf,fd->bsd", h, bp["w_down"].astype(dt))
    return x + out, (C, n, m, new_conv_buf)


def mlstm_init_state(cfg: ModelConfig, B: int):
    d_in = cfg.ssm_expand * cfg.d_model
    nh = cfg.num_heads
    hd = d_in // nh
    return (
        jnp.zeros((B, nh, hd, hd), jnp.float32),
        jnp.zeros((B, nh, hd), jnp.float32),
        jnp.full((B, nh), -1e30, jnp.float32),
        jnp.zeros((B, cfg.ssm_conv - 1, d_in), jnp.float32),  # conv buffer
    )


def slstm_seq(bp, cfg: ModelConfig, x, state=None):
    """x: (B, S, d). Returns (out (B, S, d), final state).

    The time loop runs as the Pallas kernel pair of kernels/slstm.py
    where ``kernels.ops.slstm_kernel_applies`` takes the platform and
    shape (TPU, whole time blocks), else as a ``lax.scan`` over time
    (the CPU, decode's single step, other lengths)."""
    B, S, d = x.shape
    nh = cfg.num_heads
    hd = d // nh
    dt = x.dtype
    xn = L.rmsnorm(x, bp["norm"], cfg.norm_eps)
    pre = {
        g: jnp.einsum("bsd,dhk->bshk", xn, bp[f"w_{g}"].astype(dt)).astype(
            jnp.float32
        )
        + bp[f"b_{g}"]
        for g in ("i", "f", "z", "o")
    }
    if state is None:
        state = slstm_init_state(cfg, B)
    rs = {g: bp[f"r_{g}"] for g in ("i", "f", "z", "o")}
    loop = (_slstm_kernel if kops.slstm_kernel_applies(S, B, d, hd)
            else _slstm_scan)
    hs, state = loop(pre, rs, state)
    h = hs.reshape(B, S, d).astype(dt)
    h = L.rmsnorm(h, bp["out_norm"], cfg.norm_eps)
    x = x + h
    up = jnp.einsum("bsd,dtf->bstf", h, bp["w_up"].astype(dt))
    y = jax.nn.gelu(up[..., 0, :]) * up[..., 1, :]
    return x + jnp.einsum("bsf,fd->bsd", y, bp["w_down"].astype(dt)), state


def _slstm_scan(pre, rs, state):
    """The sLSTM time loop as a ``lax.scan``, one step per token.

    pre: {g: (B, S, nh, hd) f32} gate pre-activations without the
    recurrent part; rs: {g: (nh, hd, hd)}; state: (c, n, h, m), each
    (B, nh, hd). Returns (hs (B, S, nh, hd) f32, final state)."""

    def step(carry, inp):
        c, n, h, m = carry
        ip, fp, zp, op = inp  # (B, nh, hd)
        rec = {
            g: jnp.einsum("bhk,hkj->bhj", h, rs[g]) for g in ("i", "f", "z", "o")
        }
        ip, fp, zp, op = (
            ip + rec["i"],
            fp + rec["f"],
            zp + rec["z"],
            op + rec["o"],
        )
        m_new = jnp.maximum(fp + m, ip)
        i_g = jnp.exp(ip - m_new)
        f_g = jnp.exp(fp + m - m_new)
        c = f_g * c + i_g * jnp.tanh(zp)
        n = f_g * n + i_g
        h = jax.nn.sigmoid(op) * c / jnp.maximum(n, 1e-6)
        return (c, n, h, m_new), h

    xs = tuple(pre[g].transpose(1, 0, 2, 3) for g in ("i", "f", "z", "o"))
    state, hs = lax.scan(step, state, xs)
    return hs.transpose(1, 0, 2, 3), state


def _slstm_kernel(pre, rs, state):
    """``_slstm_scan``'s contract, the time loop inside the Pallas kernel
    pair (kernels/slstm.py): the gates side by side, head-major."""
    B, S, nh, hd = pre["i"].shape
    d = nh * hd
    g = jnp.stack([pre[k] for k in "ifzo"], axis=3)  # (B, S, nh, 4, hd)
    hs, st = kops.slstm_recurrence(
        g.transpose(1, 0, 2, 3, 4).reshape(S, B, 4 * d),
        [rs[k] for k in "ifzo"], [a.reshape(B, d) for a in state])
    return (hs.transpose(1, 0, 2).reshape(B, S, nh, hd),
            tuple(a.reshape(B, nh, hd) for a in st))


def slstm_init_state(cfg: ModelConfig, B: int):
    nh = cfg.num_heads
    hd = cfg.d_model // nh
    z = jnp.zeros((B, nh, hd), jnp.float32)
    return (z, z, z, jnp.full((B, nh, hd), -1e30, jnp.float32))


# ---------------------------------------------------------------------------
# full model
# ---------------------------------------------------------------------------


def _scan_groups(params, cfg: ModelConfig, x, states=None):
    """Scan over super-blocks of (slstm_every-1) mLSTM + 1 sLSTM.

    Each block is its own checkpoint: the backward keeps only the blocks'
    inputs and recomputes one block at a time, so no block's residuals
    are stacked across the inner scan over a super-block's mLSTM blocks.
    """
    B = x.shape[0]
    G = cfg.num_layers // cfg.slstm_every
    M = cfg.slstm_every - 1
    if states is None:
        m_state = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (G, M) + a.shape),
            mlstm_init_state(cfg, B),
        )
        s_state = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (G,) + a.shape), slstm_init_state(cfg, B)
        )
    else:
        m_state, s_state = states

    remat = partial(jax.checkpoint, policy=jax.checkpoint_policies.nothing_saveable)

    @remat
    def mstep(x, minp):
        bp, st = minp
        with jax.named_scope("obs.mlstm"):
            x, st = mlstm_seq(bp, cfg, x, st)
        return x, st

    @remat
    def sblock(x, sp, ss):
        with jax.named_scope("obs.slstm"):
            return slstm_seq(sp, cfg, x, ss)

    def group(x, inp):
        mp, sp, ms, ss = inp
        x, ms = lax.scan(mstep, x, (mp, ms))
        x, ss = sblock(x, sp, ss)
        return x, (ms, ss)

    x, (m_state, s_state) = lax.scan(
        group, x, (params["mlstm"], params["slstm"], m_state, s_state)
    )
    return x, (m_state, s_state)


def forward(params, cfg: ModelConfig, batch, *, use_pallas: bool = False):
    x = L.embed_tokens(params["embed"], cfg, batch["tokens"])
    x, _ = _scan_groups(params, cfg, x)
    with jax.named_scope("obs.head"):
        x = L.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return L.lm_head(params["embed"], cfg, x), {"aux_loss": jnp.float32(0.0)}


def loss_fn(params, cfg: ModelConfig, batch, *, use_pallas: bool = False):
    logits, _ = forward(params, cfg, batch)
    with jax.named_scope("obs.head"):  # the shift of the logits too
        ce = L.cross_entropy(logits[:, :-1], batch["labels"][:, 1:])
    return ce, {"ce": ce, "aux_loss": jnp.float32(0.0)}


def prefill(params, cfg: ModelConfig, batch, cache_len: int = 0, *,
            use_pallas: bool = False):
    """Process a prompt; the recurrent states ARE the cache (O(1) size)."""
    tokens = batch["tokens"]
    x = L.embed_tokens(params["embed"], cfg, tokens)
    x, (m_state, s_state) = _scan_groups(params, cfg, x)
    x = L.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = L.lm_head(params["embed"], cfg, x)[:, -1]
    cache = {"m": m_state, "s": s_state,
             "pos": jnp.asarray(tokens.shape[1], jnp.int32)}
    return logits, cache


def init_cache(cfg: ModelConfig, batch: int, seq_len: int, dtype=None) -> dict:
    """Recurrent state — O(1) in seq_len (the point of the ssm family)."""
    G = cfg.num_layers // cfg.slstm_every
    M = cfg.slstm_every - 1
    m_state = jax.tree.map(
        lambda a: jnp.broadcast_to(a, (G, M) + a.shape),
        mlstm_init_state(cfg, batch),
    )
    s_state = jax.tree.map(
        lambda a: jnp.broadcast_to(a, (G,) + a.shape), slstm_init_state(cfg, batch)
    )
    return {"m": m_state, "s": s_state, "pos": jnp.zeros((), jnp.int32)}


def decode_step(params, cfg: ModelConfig, cache, tokens, *, use_pallas: bool = False):
    x = L.embed_tokens(params["embed"], cfg, tokens[:, None])  # (B,1,d)
    x, (m_state, s_state) = _scan_groups(
        params, cfg, x, states=(cache["m"], cache["s"])
    )
    x = L.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = L.lm_head(params["embed"], cfg, x)[:, 0]
    return logits, {"m": m_state, "s": s_state, "pos": cache["pos"] + 1}
