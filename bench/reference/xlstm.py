"""Plain float32 xLSTM language model: weights from a seed, forward, loss.

Written from the equations of arXiv:2405.04517 (exponential gating,
stabiliser state m) in the block layout the system under test uses; it
imports nothing of the system. Every matrix product runs at
``precision="highest"``, so on a TPU it is float32 and not bfloat16.

Model sizes come from a configuration dict (``bench/configs/*.json``):
num_layers, d_model, num_heads, vocab_size, ssm_expand, ssm_conv,
slstm_every, norm_eps.

Layout (every ``slstm_every``-th block an sLSTM block, the others mLSTM):

  mLSTM block:  xn = rmsnorm(x); [u, z] = xn W_up; c = silu(causal_conv(u))
                q = c W_q; k = c W_k / sqrt(hd); v = u W_v
                i~ = c w_i + b_i; f~ = c w_f + b_f (log gates)
                h = mLSTM(q, k, v, i~, f~); x + (rmsnorm(h) * silu(z)) W_down
  sLSTM block:  xn = rmsnorm(x); g~ = xn W_g + b_g for g in i, f, z, o
                h = sLSTM(g~) with recurrent R_g per head
                h = rmsnorm(h); x + h + (gelu(h W_up1) * h W_up2) W_down

Departures from the paper, which the system under test makes and this
reference therefore makes too: the forget gate is exponential (log forget
gate f~, not log-sigmoid), the mLSTM normaliser is max(|q.n|, 1) in the
stabilised units, the sLSTM normaliser is max(n, 1e-6), and the sLSTM
block's feed-forward reads the normed cell output h, not the residual
stream. gelu is the tanh approximation.

The mLSTM is computed in its parallel (quadratic) form over the whole
sequence, D_ts = exp(F_t - F_s + i~_s - m_t) for s <= t with
F = cumsum(f~) and m_t = max_{s<=t}(F_t - F_s + i~_s), which is the same
function as the recurrence and as any chunkwise form of it.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST
ein = partial(jnp.einsum, precision=HI)


def _mlstm_dims(cfg):
    d = cfg["d_model"]
    d_in = cfg["ssm_expand"] * d
    nh = cfg["num_heads"]
    return d, d_in, nh, d_in // nh


def _slstm_dims(cfg):
    d, nh = cfg["d_model"], cfg["num_heads"]
    ff = -(-int(d * 4 / 3) // 128) * 128  # projection factor 4/3, to 128
    return d, nh, d // nh, ff


# ---------------------------------------------------------------------------
# weights from a key: normal(0, 1/fan_in) projections, unit norm scales,
# zero biases except the forget gate's 3.0, embedding normal(0, 0.02^2)
# ---------------------------------------------------------------------------


def _normal(key, shape, fan_in):
    return jax.random.normal(key, shape, jnp.float32) * (
        1.0 / math.sqrt(max(1, fan_in))
    )


def _mlstm_weights(key, cfg):
    d, d_in, nh, hd = _mlstm_dims(cfg)
    ks = jax.random.split(key, 8)
    return {
        "norm": {"scale": jnp.ones((d,), jnp.float32)},
        "w_up": _normal(ks[0], (d, 2, d_in), d),
        "conv": _normal(ks[1], (cfg["ssm_conv"], d_in), cfg["ssm_conv"]),
        "wq": _normal(ks[2], (d_in, nh, hd), d_in),
        "wk": _normal(ks[3], (d_in, nh, hd), d_in),
        "wv": _normal(ks[4], (d_in, nh, hd), d_in),
        "w_i": _normal(ks[5], (d_in, nh), d_in),
        "b_i": jnp.zeros((nh,), jnp.float32),
        "w_f": _normal(ks[6], (d_in, nh), d_in),
        "b_f": jnp.ones((nh,), jnp.float32) * 3.0,
        "out_norm": {"scale": jnp.ones((d_in,), jnp.float32)},
        "w_down": _normal(ks[7], (d_in, d), d_in),
    }


def _slstm_weights(key, cfg):
    d, nh, hd, ff = _slstm_dims(cfg)
    ks = jax.random.split(key, 11)
    p = {"norm": {"scale": jnp.ones((d,), jnp.float32)},
         "out_norm": {"scale": jnp.ones((d,), jnp.float32)}}
    for gi, g in enumerate("ifzo"):
        p[f"w_{g}"] = _normal(ks[gi], (d, nh, hd), d)
        p[f"r_{g}"] = _normal(ks[4 + gi], (nh, hd, hd), hd)
        p[f"b_{g}"] = jnp.ones((nh, hd), jnp.float32) * (3.0 if g == "f" else 0.0)
    p["w_up"] = _normal(ks[8], (d, 2, ff), d)
    p["w_down"] = _normal(ks[9], (ff, d), ff)
    return p


def init_weights(key, cfg):
    """The model's float32 weights from one PRNG key. Layer weights are
    stacked: mLSTM leaves (G, M, ...), sLSTM leaves (G, ...), with G
    groups of M = slstm_every - 1 mLSTM blocks and one sLSTM block."""
    G = cfg["num_layers"] // cfg["slstm_every"]
    M = cfg["slstm_every"] - 1
    k_e, k_m, k_s = jax.random.split(key, 3)
    k1, k2 = jax.random.split(k_e)
    V, d = cfg["vocab_size"], cfg["d_model"]
    return {
        "embed": {
            "embedding": jax.random.normal(k1, (V, d)) * 0.02,
            "head": _normal(k2, (d, V), d),
        },
        "final_norm": {"scale": jnp.ones((d,), jnp.float32)},
        "mlstm": jax.vmap(jax.vmap(partial(_mlstm_weights, cfg=cfg)))(
            jax.random.split(k_m, G * M).reshape(G, M, 2)),
        "slstm": jax.vmap(partial(_slstm_weights, cfg=cfg))(
            jax.random.split(k_s, G)),
    }


# ---------------------------------------------------------------------------
# blocks
# ---------------------------------------------------------------------------


def rmsnorm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def silu(x):
    return x * jax.nn.sigmoid(x)


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def causal_conv(u, w):
    """Depthwise causal convolution: out_t = sum_j w_j u_{t-(k-1)+j}."""
    k, S = w.shape[0], u.shape[1]
    up = jnp.pad(u, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(up[:, j:j + S] * w[j] for j in range(k))


def mlstm_cell(q, k, v, ig, fg):
    """q, k, v: (B, S, nh, hd); ig, fg: (B, S, nh) log gates.
    Parallel form of the stabilised mLSTM with zero initial state."""
    S = q.shape[1]
    F = jnp.cumsum(fg, axis=1)  # (B, S, nh)
    # logD[b, h, t, s] = F_t - F_s + i_s
    logD = (F.transpose(0, 2, 1)[..., :, None]
            - F.transpose(0, 2, 1)[..., None, :]
            + ig.transpose(0, 2, 1)[..., None, :])
    causal = jnp.tril(jnp.ones((S, S), bool))
    logD = jnp.where(causal, logD, -jnp.inf)
    m = jnp.max(logD, axis=-1, keepdims=True)  # (B, nh, S, 1)
    D = jnp.exp(logD - m)
    scores = ein("bthk,bshk->bhts", q, k) * D
    num = ein("bhts,bshv->bthv", scores, v)
    den = jnp.maximum(jnp.abs(scores.sum(-1)), 1.0)  # (B, nh, S)
    return num / den.transpose(0, 2, 1)[..., None]


def mlstm_block(x, p, cfg):
    d, d_in, nh, hd = _mlstm_dims(cfg)
    B, S, _ = x.shape
    eps = cfg["norm_eps"]
    xn = rmsnorm(x, p["norm"]["scale"], eps)
    up = ein("bsd,dtf->bstf", xn, p["w_up"])
    u, z = up[..., 0, :], up[..., 1, :]
    c = silu(causal_conv(u, p["conv"]))
    q = ein("bsf,fhk->bshk", c, p["wq"])
    k = ein("bsf,fhk->bshk", c, p["wk"]) / math.sqrt(hd)
    v = ein("bsf,fhk->bshk", u, p["wv"])
    ig = ein("bsf,fh->bsh", c, p["w_i"]) + p["b_i"]
    fg = ein("bsf,fh->bsh", c, p["w_f"]) + p["b_f"]
    h = mlstm_cell(q, k, v, ig, fg).reshape(B, S, d_in)
    h = rmsnorm(h, p["out_norm"]["scale"], eps) * silu(z)
    return x + ein("bsf,fd->bsd", h, p["w_down"])


def slstm_cell(pre, R):
    """pre: gate pre-activations {g: (B, S, nh, hd)}; R: {g: (nh, hd, hd)}.
    Stabilised sLSTM recurrence from zero state (m_0 = -inf). The four
    recurrent products of a step are one product with the R_g side by
    side."""
    B, _, nh, hd = pre["i"].shape
    zero = jnp.zeros((B, nh, hd), jnp.float32)
    r = jnp.concatenate([R[g] for g in "ifzo"], axis=-1)  # (nh, hd, 4 hd)

    def step(carry, x):
        c, n, h, m = carry
        g = x + ein("bhk,hkj->bhj", h, r)
        gi, gf, gz, go = jnp.split(g, 4, axis=-1)
        m_new = jnp.maximum(gf + m, gi)
        i_g = jnp.exp(gi - m_new)
        f_g = jnp.exp(gf + m - m_new)
        c = f_g * c + i_g * jnp.tanh(gz)
        n = f_g * n + i_g
        h = jax.nn.sigmoid(go) * c / jnp.maximum(n, 1e-6)
        return (c, n, h, m_new), h

    xs = jnp.concatenate([pre[g] for g in "ifzo"], axis=-1).transpose(
        1, 0, 2, 3)  # (S, B, nh, 4 hd)
    init = (zero, zero, zero, jnp.full((B, nh, hd), -jnp.inf, jnp.float32))
    _, hs = lax.scan(step, init, xs, unroll=4)
    return hs.transpose(1, 0, 2, 3)  # (B, S, nh, hd)


def slstm_block(x, p, cfg):
    d, nh, hd, ff = _slstm_dims(cfg)
    B, S, _ = x.shape
    eps = cfg["norm_eps"]
    xn = rmsnorm(x, p["norm"]["scale"], eps)
    pre = {g: ein("bsd,dhk->bshk", xn, p[f"w_{g}"]) + p[f"b_{g}"]
           for g in "ifzo"}
    h = slstm_cell(pre, {g: p[f"r_{g}"] for g in "ifzo"}).reshape(B, S, d)
    h = rmsnorm(h, p["out_norm"]["scale"], eps)
    up = ein("bsd,dtf->bstf", h, p["w_up"])
    y = gelu_tanh(up[..., 0, :]) * up[..., 1, :]
    return x + h + ein("bsf,fd->bsd", y, p["w_down"])


# ---------------------------------------------------------------------------
# model
# ---------------------------------------------------------------------------


def logits(params, tokens, cfg):
    """tokens (B, S) int32 -> logits (B, S, V) float32. Each mLSTM block
    is recomputed in the backward pass (jax.checkpoint), which changes no
    number and keeps one such block's (B, nh, S, S) activations live, so
    that the reference fits one chip beside its own float32 weights and
    gradients. The sLSTM blocks keep theirs: recomputing their serial
    recurrence would cost more time than their few activations cost
    memory."""
    x = params["embed"]["embedding"][tokens]
    mblock = jax.checkpoint(partial(mlstm_block, cfg=cfg))
    sblock = partial(slstm_block, cfg=cfg)

    def group(x, gp):
        mp, sp = gp
        x, _ = lax.scan(lambda x, p: (mblock(x, p), None), x, mp)
        return sblock(x, sp), None

    x, _ = lax.scan(group, x, (params["mlstm"], params["slstm"]))
    x = rmsnorm(x, params["final_norm"]["scale"], cfg["norm_eps"])
    return ein("bsd,dv->bsv", x, params["embed"]["head"])


def loss(params, tokens, cfg):
    """Mean next-token cross-entropy over B x (S - 1) positions."""
    lg = logits(params, tokens, cfg)[:, :-1]
    labels = tokens[:, 1:]
    logp = lg - jax.nn.logsumexp(lg, axis=-1, keepdims=True)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return nll.mean()
