"""Jit'd public wrappers around the Pallas kernels.

Dispatch policy: on TPU the compiled kernels run natively; everywhere else
(CPU runs, tests) they run in ``interpret=True`` mode, which executes the
same kernel body per-block in Python/XLA — bit-comparable logic, no TPU
required. The pure-jnp oracles live in ref.py.

Whether the meta path uses the kernels at all is the ``use_pallas`` field
of ``MAvgConfig``/``CommConfig``; left at None it is decided here from the
platform (``resolve_use_pallas``): kernels on TPU, the jnp oracle
elsewhere.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import block_momentum as _bm
from repro.kernels import flash_attention as _fa
from repro.kernels import fused_meta as _fm
from repro.kernels import local_sgd as _sgd
from repro.kernels import neighbor_mix as _nm
from repro.kernels import pack_update as _pu
from repro.kernels import quantize as _q
from repro.kernels import ref as _ref
from repro.kernels import robust_reduce as _rr
from repro.kernels import slstm as _sl

LANES = 128


def _platform() -> str:
    """The platform that work traced now runs on: the device of a
    ``jax.default_device`` context (a CPU reference on a TPU host), else
    the default backend."""
    dev = jax.config.jax_default_device
    if dev is None:
        return jax.default_backend()
    return dev if isinstance(dev, str) else dev.platform


def _default_interpret() -> bool:
    return _platform() != "tpu"


def resolve_use_pallas(flag) -> bool:
    """A config's ``use_pallas``: an explicit True/False is kept; None
    means "compiled kernels on TPU, the jnp oracle elsewhere"."""
    return _platform() == "tpu" if flag is None else bool(flag)


# ---------------------------------------------------------------------------
# layout helpers: leaf <-> (rows, 128) padded 2-D
# ---------------------------------------------------------------------------


def _layout(n: int) -> tuple[int, int]:
    """(rows, pad) of the (rows, 128) wire layout for an n-element leaf —
    computed once per call site; same-shaped operands share it."""
    rows = -(-n // LANES)
    rows = -(-rows // 8) * 8  # sublane multiple
    return rows, rows * LANES - n


def _to_2d_as(x, rows: int, pad: int):
    """Apply a precomputed layout to one operand."""
    return jnp.pad(x.reshape(-1), (0, pad)).reshape(rows, LANES)


def _to_2d(x):
    rows, pad = _layout(x.size)
    return _to_2d_as(x, rows, pad), x.shape, x.size


def _from_2d(x2, shape, n):
    return x2.reshape(-1)[:n].reshape(shape)


def is_packed_plane(x) -> bool:
    """Is ``x`` one lane-aligned (rows, 128) plane — the packed flat
    meta-plane layout every kernel here takes (repro.pack)? The single
    dispatch predicate: ops' fast paths skip the reshape/pad round trip
    on it, and repro.topology routes packed states through the fused
    kernels with it (the shape check, not just the type, keeps bare-array
    param pytrees that don't carry the wire layout on the generic
    per-leaf path)."""
    return (isinstance(x, jax.Array) and x.ndim == 2
            and x.shape[1] == LANES and x.shape[0] % 8 == 0)


# ---------------------------------------------------------------------------
# block momentum
# ---------------------------------------------------------------------------


def block_momentum(w, v, a, *, mu, eta=1.0, nesterov=False, interpret=None):
    """Fused meta update on one array. Returns (w', v')."""
    interpret = _default_interpret() if interpret is None else interpret
    if is_packed_plane(w):  # packed meta plane: feed the kernel directly
        return _bm.block_momentum_2d(
            w, v, a, mu, eta, nesterov=nesterov, interpret=interpret
        )
    rows, pad = _layout(w.size)  # w/v/a are same-shaped: one layout
    w2, v2, a2 = (_to_2d_as(t, rows, pad) for t in (w, v, a))
    w2n, v2n = _bm.block_momentum_2d(
        w2, v2, a2, mu, eta, nesterov=nesterov, interpret=interpret
    )
    return _from_2d(w2n, w.shape, w.size), _from_2d(v2n, v.shape, v.size)


def block_momentum_tree(gp, v, avg, *, mu, eta=1.0, nesterov=False,
                        interpret=None):
    """Apply the fused update leaf-wise over a parameter pytree."""
    flat_gp, treedef = jax.tree_util.tree_flatten(gp)
    flat_v = treedef.flatten_up_to(v)
    flat_avg = treedef.flatten_up_to(avg)
    new_w, new_v = [], []
    for wi, vi, ai in zip(flat_gp, flat_v, flat_avg):
        wn, vn = block_momentum(
            wi, vi, ai, mu=mu, eta=eta, nesterov=nesterov, interpret=interpret
        )
        new_w.append(wn)
        new_v.append(vn)
    return (
        jax.tree_util.tree_unflatten(treedef, new_w),
        jax.tree_util.tree_unflatten(treedef, new_v),
    )


# ---------------------------------------------------------------------------
# gossip neighbor mix (repro.topology)
# ---------------------------------------------------------------------------


# the single stack-selection implementation lives next to the kernel
mixing_matrix_at = _nm.mixing_matrix_at


def _resolve_matrix(w, step):
    if w.ndim == 3:
        if step is None:
            raise ValueError(
                "got a (T, L, L) mixing-matrix stack but no step= — the "
                "time-varying graphs are step-indexed; pass the meta step "
                "(silently using step 0 would freeze the graph)"
            )
        return mixing_matrix_at(w, step)
    return w


def neighbor_mix(x, w, *, interpret=None, step=None):
    """Mix one (L, ...) learner stack with the (L, L) matrix w — or, for
    the time-varying graphs, a (T, L, L) stack indexed by ``step`` — in a
    single HBM pass. Returns sum_k w_jk x_k, same shape/dtype as x."""
    interpret = _default_interpret() if interpret is None else interpret
    w = _resolve_matrix(w, step)
    L = x.shape[0]
    flat = x.astype(jnp.float32).reshape(L, -1)
    n = flat.shape[1]
    rows = -(-n // LANES)
    rows = -(-rows // 8) * 8
    x3 = jnp.pad(flat, ((0, 0), (0, rows * LANES - n))).reshape(L, rows, LANES)
    mixed = _nm.neighbor_mix_3d(x3, w, interpret=interpret)
    return mixed.reshape(L, -1)[:, :n].reshape(x.shape).astype(x.dtype)


def neighbor_mix_tree(tree, w, *, use_pallas=True, interpret=None, step=None):
    """Apply the gossip mix leaf-wise over a stacked (L, ...) pytree.

    ``w`` may be a (T, L, L) stack (time-varying graph, requires
    ``step``); the step's matrix is selected once here, not per leaf.
    """
    w = _resolve_matrix(w, step)
    if not use_pallas:
        return jax.tree.map(lambda x: _ref.neighbor_mix_ref(x, w), tree)
    return jax.tree.map(
        lambda x: neighbor_mix(x, w, interpret=interpret), tree
    )


# ---------------------------------------------------------------------------
# fused SGD apply
# ---------------------------------------------------------------------------


def sgd_apply(w, g, lr, *, interpret=None):
    interpret = _default_interpret() if interpret is None else interpret
    if is_packed_plane(w):  # packed meta plane: feed the kernel directly
        return _sgd.sgd_apply_2d(w, g, lr, interpret=interpret)
    rows, pad = _layout(w.size)  # w/g are same-shaped: one layout
    out = _sgd.sgd_apply_2d(
        _to_2d_as(w, rows, pad), _to_2d_as(g, rows, pad), lr,
        interpret=interpret,
    )
    return _from_2d(out, w.shape, w.size)


# ---------------------------------------------------------------------------
# displacement quantization (repro.comm wire compression)
# ---------------------------------------------------------------------------


def quantize(x, key, *, qmax=127, block=None, use_pallas=True, interpret=None):
    """Quantize any-shaped ``x`` to (q int8 2-D, per-chunk scales).

    Returns (q, scales, shape, n) — feed the last three to ``dequantize``.
    """
    interpret = _default_interpret() if interpret is None else interpret
    x2, shape, n = _to_2d(x.astype(jnp.float32))
    b = _q.choose_block(x2.shape[0], block)
    u2 = jax.random.uniform(key, x2.shape, jnp.float32)
    if use_pallas:
        q, s = _q.quantize_2d(x2, u2, qmax=qmax, block=b, interpret=interpret)
    else:
        q, s = _ref.quantize_ref(x2, u2, qmax, b)
    return q, s, shape, n


def dequantize(q, scales, shape, n, *, use_pallas=True, interpret=None):
    interpret = _default_interpret() if interpret is None else interpret
    if use_pallas:
        dq = _q.dequantize_2d(q, scales, interpret=interpret)
    else:
        dq = _ref.dequantize_ref(q, scales)
    return _from_2d(dq, shape, n)


def quant_dequant(x, key, *, dtype="int8", block=None, use_pallas=True,
                  interpret=None):
    """Round-trip wire compression of one leaf.

    Returns (x-like f32 after quant->dequant, n_scale_chunks). ``dtype``:
    int8 | int4 (stochastic-rounding Pallas kernels) | fp8 (jnp
    per-chunk-scaled e4m3 cast).
    """
    if dtype == "fp8":
        x2, shape, n = _to_2d(x.astype(jnp.float32))
        b = _q.choose_block(x2.shape[0], block)
        return _from_2d(_ref.fp8_roundtrip_ref(x2, b), shape, n), x2.shape[0] // b
    qmax = {"int8": 127, "int4": 7}[dtype]
    q, s, shape, n = quantize(x, key, qmax=qmax, block=block,
                              use_pallas=use_pallas, interpret=interpret)
    return dequantize(q, s, shape, n, use_pallas=use_pallas,
                      interpret=interpret), s.shape[0]


# ---------------------------------------------------------------------------
# fused packed-plane compressed displacement (repro.pack meta step)
# ---------------------------------------------------------------------------


def pack_update(w, g, e, u, *, qmax=127, block=None, use_pallas=True,
                interpret=None):
    """Fused displacement + EF add + stochastic-rounding quantize over the
    packed (L, rows, 128) learner plane against the (rows, 128) meta
    params — one HBM pass instead of the per-leaf path's three
    (kernels/pack_update.py; jnp oracle in ref.py shares the dither and
    chunk geometry, so the two routes agree to one scale ulp with
    bit-identical rounding decisions).

    Returns (c, err, scales) — see pack_update_3d.
    """
    interpret = _default_interpret() if interpret is None else interpret
    L, rows, lanes = w.shape
    b = _q.choose_block(rows, block)
    if use_pallas:
        return _pu.pack_update_3d(w, g, e, u, qmax=qmax, block=b,
                                  interpret=interpret)
    return _ref.pack_update_ref(w, g, e, u, qmax, b)


def pack_compress(d, u, *, qmax=127, block=None, with_err=True,
                  use_pallas=True, interpret=None):
    """Compress-only variant of ``pack_update`` for an already-formed
    (L, rows, 128) displacement plane — the gossip / masked-hierarchical
    compress-stage path. Skips the gp-plane read (the caller had to
    synthesize zeros just to satisfy pack_update's signature), and under
    ``with_err=False`` (no error feedback: nobody reads the residual)
    also skips the err-plane write: 2R+3W or 2R+2W instead of 3R+3W,
    bitwise-identical outputs.

    Returns (c, err, scales) — ``err`` is the EF residual computed in the
    same pass (delta - c), so the error-feedback route needs no extra
    subtraction pass either; None when ``with_err`` is off.
    """
    interpret = _default_interpret() if interpret is None else interpret
    L, rows, lanes = d.shape
    b = _q.choose_block(rows, block)
    if use_pallas:
        return _pu.pack_compress_3d(d, u, qmax=qmax, block=b,
                                    with_err=with_err, interpret=interpret)
    return _ref.pack_compress_ref(d, u, qmax, b, with_err=with_err)


# ---------------------------------------------------------------------------
# robust learner-stack reduction (repro.robust)
# ---------------------------------------------------------------------------


median_trim = _rr.median_trim


def robust_reduce(x, *, trim=0, block=None, use_pallas=True, interpret=None):
    """Coordinate-wise trimmed mean over the leading (learner) axis of a
    stacked plane: drop the ``trim`` largest and smallest values per
    coordinate, average the rest. ``trim=0`` is bitwise the plain mean
    (the parity contract every existing invariant rides on);
    ``trim=median_trim(L)`` is the coordinate-wise median.

    Packed (L, rows, 128) stacks route through the fused Pallas kernel
    (one HBM pass, the sort stays in VMEM); everything else takes the jnp
    oracle, which is also the per-leaf path for unpacked pytrees.
    """
    interpret = _default_interpret() if interpret is None else interpret
    if (use_pallas and x.ndim == 3 and x.shape[2] == LANES
            and x.shape[1] % 8 == 0):
        b = _q.choose_block(x.shape[1], block)
        return _rr.robust_reduce_3d(x, trim=trim, block=b,
                                    interpret=interpret)
    return _ref.robust_reduce_ref(x, trim)


def robust_reduce_tree(tree, *, trim=0, use_pallas=True, interpret=None):
    """Apply the robust reduction leaf-wise over a stacked (L, ...) pytree."""
    return jax.tree.map(
        lambda x: robust_reduce(x, trim=trim, use_pallas=use_pallas,
                                interpret=interpret),
        tree,
    )


# ---------------------------------------------------------------------------
# fused momentum -> learner broadcast (repro.pack meta step)
# ---------------------------------------------------------------------------


def fused_momentum_broadcast(w, v, a, *, mu, eta=1.0, num_learners,
                             ldtype=None, nesterov=False, use_pallas=True,
                             interpret=None):
    """Block momentum + learner reset on the packed (rows, 128) meta
    plane in one HBM pass: v' = mu v + eta (a - w); w' = w + v'; and the
    (L, rows, 128) learner plane w'.astype(ldtype) emitted directly from
    the update's VMEM tile (kernels/fused_meta.py) — eliminating
    tree_broadcast_learners' re-read of the meta params.

    Returns (w', v', learners). Bit-identical to block_momentum followed
    by astype + broadcast (the jnp oracle shares the op order).
    """
    interpret = _default_interpret() if interpret is None else interpret
    assert is_packed_plane(w), w.shape
    ldtype = w.dtype if ldtype is None else ldtype
    if use_pallas:
        return _fm.fused_momentum_broadcast_2d(
            w, v, a, mu, eta, num_learners, ldtype, nesterov=nesterov,
            interpret=interpret,
        )
    return _ref.fused_momentum_broadcast_ref(
        w, v, a, mu, eta, num_learners, ldtype, nesterov=nesterov
    )


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------


def flash_attention(q, k, v, *, causal=True, sliding_window=0,
                    prefix_global=0, interpret=None):
    """q: (B, S, H, D); k, v: (B, S, KV, D) -> (B, S, H, D).

    Pads D to a lane multiple and S to a block multiple; GQA is handled
    inside the kernel via BlockSpec index maps (no repeated K/V).
    """
    interpret = _default_interpret() if interpret is None else interpret
    B, S, H, D = q.shape
    KV = k.shape[2]
    scale = 1.0 / (D ** 0.5)

    d_pad = -(-D // LANES) * LANES
    bq = min(_fa.DEFAULT_BLOCK_Q, max(8, S))
    while S % bq:
        bq //= 2
    bk = min(_fa.DEFAULT_BLOCK_K, max(8, S))
    while S % bk:
        bk //= 2

    def prep(x, nh):
        x = jnp.pad(x, ((0, 0), (0, 0), (0, 0), (0, d_pad - D)))
        return x.transpose(0, 2, 1, 3).reshape(B * nh, S, d_pad)

    out = _fa.flash_attention_bhsd(
        prep(q, H), prep(k, KV), prep(v, KV),
        causal=causal, sliding_window=sliding_window,
        prefix_global=prefix_global, scale=scale,
        block_q=bq, block_k=bk, interpret=interpret,
    )
    out = out.reshape(B, H, S, d_pad).transpose(0, 2, 1, 3)[..., :D]
    return out


# ---------------------------------------------------------------------------
# sLSTM time loop (models/xlstm.py slstm_seq)
# ---------------------------------------------------------------------------


def slstm_kernel_applies(seq_len: int, batch: int, d: int,
                         head_dim: int) -> bool:
    """Whether ``slstm_seq`` runs its time loop as the Pallas kernel pair
    (kernels/slstm.py) rather than as a ``lax.scan``: on a TPU, for a
    shape the kernels' tiling takes, at the default matmul precision (the
    kernels' product is the default's one bf16 pass) and outside a
    multi-device mesh (XLA cannot partition a Mosaic kernel)."""
    mesh = jax.sharding.get_abstract_mesh()
    return (resolve_use_pallas(None)
            and jax.config.jax_default_matmul_precision is None
            and (mesh.empty or mesh.size == 1)
            and _sl.fits(seq_len, batch, d, head_dim))


def slstm_recurrence(g, rs, state, *, time_block=_sl.TIME_BLOCK,
                     interpret=None):
    """The sLSTM recurrence as the kernel pair (differentiable).

    g: (S, B, 4 d) f32 gate pre-activations, head-major then gate;
    rs: (r_i, r_f, r_z, r_o), each (nh, hd, hd); state: (c, n, h, m),
    each (B, d) f32. Returns (hs (S, B, d), final (c, n, h, m))."""
    interpret = _default_interpret() if interpret is None else interpret
    return _sl.recurrence(g, tuple(rs), tuple(state), time_block, interpret)
