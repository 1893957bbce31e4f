"""The sLSTM time loop as one Pallas kernel pair, forward and backward
(xLSTM, arXiv:2405.04517; models/xlstm.py ``slstm_seq``).

A step of the recurrence is four small per-head products h_{t-1} R_g and
a few elementwise ops; as a ``lax.scan`` over S steps each step pays the
XLA while loop's slicing and writing of its inputs and outputs, which is
most of the loop's time at xlstm-350m width (PERF.md, section 5). Here
the time loop runs inside the kernel: the recurrent weights and the
carried state stay in VMEM, and the grid walks time blocks of
``time_block`` steps in order, the state carried across grid steps in
the resident final-state output blocks.

Layout: the gate pre-activations are one (S, B, 4 d) plane, head-major
then gate (column = head * 4 hd + gate * hd + k, gates i, f, z, o), and
the recurrent weights are concatenated per head to (nh, hd, 4 hd), so a
head's four gates come from one (B, hd) @ (hd, 4 hd) product.

Forward residuals: the full pre-activations (input + recurrent product)
and the states c, n, m entering each step. The backward recomputes every
gate from them elementwise, runs the reverse time loop carrying only the
state cotangents (dc, dn, dh, dm), and writes the gate pre-activation
cotangents delta_t; the dgrad delta_t R^T is the loop's one product per
step. The weight cotangent dR = sum_{b,t} h_{t-1}^T delta_t is one
einsum over B*S after the kernel, not an accumulation inside the loop.

Precision: the recurrent product's operands are rounded to ``mxu_dtype``
and accumulated in f32. On TPU that is bfloat16, one MXU pass: what the
scan path's f32 einsum at the default precision compiles to (its
compiled HLO converts h to bf16 before the product). In interpret mode
on the CPU it is float32, as XLA's CPU dot is, so the two paths agree to
f32 rounding in the tests.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TIME_BLOCK = 16
LANES = 128
EPS = 1e-6  # the normaliser's floor, max(n, EPS), as in the scan path
VMEM_BUDGET = 64 * 2**20  # v5e has 128 MiB of VMEM per core


def _vmem_bytes(B: int, d: int, hd: int, time_block: int) -> int:
    """VMEM either kernel asks for: double-buffered time blocks of 12 d
    f32 lanes per step over B rows padded to the 8-row sublane tile, the
    double-buffered (nh, hd, 4 hd) weights at 4 B at most, and the
    resident (B, d) state blocks."""
    rows = -(-B // 8) * 8
    blocks = 2 * time_block * rows * 12 * d * 4
    return blocks + 2 * 4 * d * hd * 4 + 2 * 8 * rows * d * 4


def fits(seq_len: int, batch: int, d: int, head_dim: int,
         time_block: int = TIME_BLOCK) -> bool:
    """Whether the kernel pair's tiling takes this shape: whole time
    blocks, lane-aligned heads, and blocks that fit the VMEM budget."""
    return (seq_len % time_block == 0 and head_dim % LANES == 0
            and _vmem_bytes(batch, d, head_dim, time_block) <= VMEM_BUDGET)


def _cell(g, c_prev, n_prev, m_prev, hd):
    """One head's step from its full pre-activations g (B, 4 hd): the
    same ops, in the same order, as the scan path's step."""
    ip, fp, zp, op = (g[:, i * hd:(i + 1) * hd] for i in range(4))
    m = jnp.maximum(fp + m_prev, ip)
    i_g = jnp.exp(ip - m)
    f_g = jnp.exp(fp + m_prev - m)
    z = jnp.tanh(zp)
    c = f_g * c_prev + i_g * z
    n = f_g * n_prev + i_g
    o = jax.nn.sigmoid(op)
    nn = jnp.maximum(n, EPS)
    h = o * c / nn
    return ip, fp, m, i_g, f_g, z, o, c, n, nn, h


def _max_weight(x, other, out):
    """JAX's derivative of max(x, other) with respect to x at ``out``:
    1 where x wins, 1/2 on a tie, 0 where it loses."""
    one, zero = jnp.ones_like(out), jnp.zeros_like(out)
    return (jnp.where(x == out, one, zero)
            / jnp.where(other == out, one + one, one))


def _fwd_kernel(g_ref, r_ref, c0_ref, n0_ref, h0_ref, m0_ref,
                h_ref, gf_ref, cp_ref, np_ref, mp_ref,
                c_ref, n_ref, hl_ref, m_ref, *, nh, hd, mxu_dtype):
    @pl.when(pl.program_id(0) == 0)
    def _():
        c_ref[...] = c0_ref[...]
        n_ref[...] = n0_ref[...]
        hl_ref[...] = h0_ref[...]
        m_ref[...] = m0_ref[...]

    def step(j, carry):
        h_prev = hl_ref[...]
        c_prev, n_prev, m_prev = c_ref[...], n_ref[...], m_ref[...]
        cp_ref[j] = c_prev
        np_ref[j] = n_prev
        mp_ref[j] = m_prev
        for k in range(nh):
            s = slice(k * hd, (k + 1) * hd)
            gs = slice(4 * k * hd, 4 * (k + 1) * hd)
            g = g_ref[j, :, gs] + jnp.dot(
                h_prev[:, s].astype(mxu_dtype), r_ref[k],
                preferred_element_type=jnp.float32)
            gf_ref[j, :, gs] = g
            _, _, m, _, _, _, _, c, n, _, h = _cell(
                g, c_prev[:, s], n_prev[:, s], m_prev[:, s], hd)
            c_ref[:, s] = c
            n_ref[:, s] = n
            m_ref[:, s] = m
            hl_ref[:, s] = h
            h_ref[j, :, s] = h
        return carry

    lax.fori_loop(0, g_ref.shape[0], step, 0)


def _bwd_kernel(gf_ref, cp_ref, np_ref, mp_ref, dh_ref, rt_ref,
                dcT_ref, dnT_ref, dhT_ref, dmT_ref,
                dg_ref, dc_ref, dn_ref, dhl_ref, dm_ref, *, nh, hd,
                mxu_dtype):
    @pl.when(pl.program_id(0) == 0)
    def _():
        dc_ref[...] = dcT_ref[...]
        dn_ref[...] = dnT_ref[...]
        dhl_ref[...] = dhT_ref[...]
        dm_ref[...] = dmT_ref[...]

    T = gf_ref.shape[0]

    def step(jj, carry):
        j = T - 1 - jj
        dh_all = dhl_ref[...] + dh_ref[j]  # cotangent of h_t
        dc_all, dn_all, dm_all = dc_ref[...], dn_ref[...], dm_ref[...]
        c_all, n_all, m_all = cp_ref[j], np_ref[j], mp_ref[j]
        for k in range(nh):
            s = slice(k * hd, (k + 1) * hd)
            gs = slice(4 * k * hd, 4 * (k + 1) * hd)
            c_prev, n_prev, m_prev = c_all[:, s], n_all[:, s], m_all[:, s]
            ip, fp, m, i_g, f_g, z, o, c, n, nn, _ = _cell(
                gf_ref[j, :, gs], c_prev, n_prev, m_prev, hd)
            dh = dh_all[:, s]
            # h = (o * c) / nn
            doc = dh / nn
            dnn = -dh * (o * c) / (nn * nn)
            dc = dc_all[:, s] + doc * o
            dn = dn_all[:, s] + dnn * _max_weight(n, EPS, nn)
            dop = doc * c * (o * (1.0 - o))
            dzp = dc * i_g * ((1.0 + z) * (1.0 - z))
            a_i = (dc * z + dn) * i_g  # through exp(ip - m)
            a_f = (dc * c_prev + dn * n_prev) * f_g  # exp(fp + m_prev - m)
            dmax = dm_all[:, s] - a_i - a_f  # m = max(fp + m_prev, ip)
            from_f = dmax * _max_weight(fp + m_prev, ip, m)
            dip = a_i + dmax * _max_weight(ip, fp + m_prev, m)
            dfp = a_f + from_f
            delta = jnp.concatenate([dip, dfp, dzp, dop], axis=-1)
            dg_ref[j, :, gs] = delta
            dhl_ref[:, s] = jnp.dot(
                delta.astype(mxu_dtype), rt_ref[k],
                preferred_element_type=jnp.float32)
            dc_ref[:, s] = dc * f_g
            dn_ref[:, s] = dn * f_g
            dm_ref[:, s] = dfp
        return carry

    lax.fori_loop(0, T, step, 0)


def _compiler_params(B, d, hd, time_block):
    return pltpu.CompilerParams(
        dimension_semantics=("arbitrary",),
        vmem_limit_bytes=_vmem_bytes(B, d, hd, time_block) + 8 * 2**20)


def _fwd_call(g, rcat, state, time_block, interpret, mxu_dtype):
    S, B, d4 = g.shape
    nh, hd, _ = rcat.shape
    d = d4 // 4
    t_spec = lambda w: pl.BlockSpec((time_block, B, w), lambda k: (k, 0, 0))
    s_spec = pl.BlockSpec((B, d), lambda k: (0, 0))
    seq = lambda w: jax.ShapeDtypeStruct((S, B, w), jnp.float32)
    st = jax.ShapeDtypeStruct((B, d), jnp.float32)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, nh=nh, hd=hd, mxu_dtype=mxu_dtype),
        grid=(S // time_block,),
        in_specs=[t_spec(d4), pl.BlockSpec(rcat.shape, lambda k: (0, 0, 0))]
        + [s_spec] * 4,
        out_specs=[t_spec(d), t_spec(d4)] + [t_spec(d)] * 3 + [s_spec] * 4,
        out_shape=[seq(d), seq(d4)] + [seq(d)] * 3 + [st] * 4,
        compiler_params=_compiler_params(B, d, hd, time_block),
        interpret=interpret,
        name="slstm_fwd",
    )(g, rcat, *state)


def _bwd_call(gf, cp, np_, mp, dhs, rt, dstate, time_block, interpret,
              mxu_dtype):
    S, B, d4 = gf.shape
    nh = rt.shape[0]
    d = d4 // 4
    last = S // time_block - 1
    t_spec = lambda w: pl.BlockSpec((time_block, B, w),
                                    lambda k: (last - k, 0, 0))
    s_spec = pl.BlockSpec((B, d), lambda k: (0, 0))
    st = jax.ShapeDtypeStruct((B, d), jnp.float32)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, nh=nh, hd=d // nh,
                          mxu_dtype=mxu_dtype),
        grid=(S // time_block,),
        in_specs=[t_spec(d4)] + [t_spec(d)] * 4
        + [pl.BlockSpec(rt.shape, lambda k: (0, 0, 0))] + [s_spec] * 4,
        out_specs=[t_spec(d4)] + [s_spec] * 4,
        out_shape=[jax.ShapeDtypeStruct((S, B, d4), jnp.float32)] + [st] * 4,
        compiler_params=_compiler_params(B, d, d // nh, time_block),
        interpret=interpret,
        name="slstm_bwd",
    )(gf, cp, np_, mp, dhs, rt, *dstate)


def _mxu_dtype(interpret: bool):
    return jnp.float32 if interpret else jnp.bfloat16


def _rcat(rs, dtype):
    """(r_i, r_f, r_z, r_o), each (nh, hd, hd) -> (nh, hd, 4 hd)."""
    return jnp.concatenate([r.astype(dtype) for r in rs], axis=-1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def recurrence(g, rs, state, time_block, interpret):
    """The sLSTM recurrence over a sequence.

    g: (S, B, 4 d) f32 gate pre-activations without the recurrent part,
    head-major then gate; rs: (r_i, r_f, r_z, r_o), each (nh, hd, hd);
    state: (c, n, h, m), each (B, d) f32. Returns (hs (S, B, d) f32, the
    final (c, n, h, m))."""
    return _recurrence_fwd(g, rs, state, time_block, interpret)[0]


def _recurrence_fwd(g, rs, state, time_block, interpret):
    mxu = _mxu_dtype(interpret)
    hs, gf, cp, np_, mp, c, n, h, m = _fwd_call(
        g, _rcat(rs, mxu), state, time_block, interpret, mxu)
    return (hs, (c, n, h, m)), (gf, cp, np_, mp, hs, state[2], rs)


def _recurrence_bwd(time_block, interpret, res, ct):
    gf, cp, np_, mp, hs, h0, rs = res
    dhs, dstate = ct
    S, B, d4 = gf.shape
    nh, hd, _ = rs[0].shape
    mxu = _mxu_dtype(interpret)
    rt = _rcat(rs, mxu).transpose(0, 2, 1)  # (nh, 4 hd, hd)
    dg, dc0, dn0, dh0, dm0 = _bwd_call(
        gf, cp, np_, mp, dhs, rt, dstate, time_block, interpret, mxu)
    # dR = sum over (b, t) of h_{t-1}^T delta_t: one product after the loop
    h_prev = jnp.concatenate([h0[None], hs[:-1]], axis=0)
    dr = jnp.einsum("sbhk,sbhj->hkj", h_prev.reshape(S, B, nh, hd),
                    dg.reshape(S, B, nh, 4 * hd))
    drs = tuple(dr[..., i * hd:(i + 1) * hd].astype(r.dtype)
                for i, r in enumerate(rs))
    return dg, drs, (dc0, dn0, dh0, dm0)


recurrence.defvjp(_recurrence_fwd, _recurrence_bwd)
