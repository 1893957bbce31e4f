"""Zero-copy meta phase acceptance tests (DESIGN.md §10).

Invariants:
  ZC1  donation parity: the donated meta step (jax.jit donate_argnums on
       the MetaState) is bitwise the non-donated step over 10
       meta-iterations, for flat / hierarchical / gossip x dense /
       int8+EF — donation is pure buffer aliasing, never numerics.
  ZC2  donation contract: a donated input state is dead after the call
       (re-use raises), and make_jit_meta_step gates donation on
       MAvgConfig.donate.
  ZC3  fused momentum->broadcast: the oracle route is bit-identical to
       the unfused two-step path it replaces (block_momentum_update then
       cast + tree_broadcast_learners); the Pallas kernel matches the
       oracle at the repo's kernel tolerance (CPU FMA contraction differs
       between separately compiled programs, same as block_momentum); and
       within each route the learner plane is exactly the cast broadcast
       of the new meta params.
  ZC4  compress-only kernel: pack_compress == pack_update on a zero gp
       plane, bitwise, on both the kernel and oracle routes; the EF
       residual it emits equals the separate tree_sub(delta, c) pass it
       replaces, bitwise (CompressedReducer._compress_residual).
  ZC5  on xlstm-350m's full-width planes (abstract, nothing allocated):
       donation cuts the meta mix's compiled peak state to <= 0.6x the
       functional program's; the fused kernel removes the unfused route's
       read-back of the new meta plane; compress-only moves >= 1 plane less
       than pack_update on a zero gp plane.
"""
import dataclasses as dc

import jax
import jax.extend
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import CommConfig, MAvgConfig, TopologyConfig
from repro.core.meta import STATE_ARGNUM, init_state, make_jit_meta_step, make_meta_step
from repro.kernels import ops, ref
from repro.models.simple import mlp_init, mlp_loss

D, C, H = 8, 4, 16
PARAMS = mlp_init(jax.random.PRNGKey(0), D, H, C)
RNG = np.random.RandomState(11)


def _batches(seed, L, K, B=4):
    kx, ky = jax.random.split(jax.random.PRNGKey(seed))
    return {
        "x": jax.random.normal(kx, (L, K, B, D)),
        "y": jax.random.randint(ky, (L, K, B), 0, C),
    }


def _bitwise(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _cfg(topo_kind: str, scheme: str) -> MAvgConfig:
    comm = CommConfig(scheme=scheme, error_feedback=(scheme != "dense"))
    if topo_kind == "flat":
        return MAvgConfig(algorithm="mavg", num_learners=4, k_steps=2,
                          learner_lr=0.1, momentum=0.6, comm=comm)
    topo = (TopologyConfig(kind="hierarchical", groups=2, outer_every=2,
                           outer_momentum=0.3, inner_comm=comm)
            if topo_kind == "hierarchical"
            else TopologyConfig(kind="gossip", graph="exponential",
                                momentum_tracking=True, inner_comm=comm))
    return MAvgConfig(algorithm="mavg", num_learners=4, k_steps=2,
                      learner_lr=0.1, momentum=0.6, topology=topo)


# ---------------------------------------------------------------------------
# ZC1: donated == non-donated, bitwise, 10 meta-iterations
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("topo_kind", ["flat", "hierarchical", "gossip"])
@pytest.mark.parametrize("scheme", ["dense", "int8"])
def test_zc1_donation_parity_bitwise(topo_kind, scheme):
    cfg = _cfg(topo_kind, scheme)
    finals = {}
    for donate in (False, True):
        state = init_state(PARAMS, cfg)
        step = make_jit_meta_step(mlp_loss, cfg, donate=donate)
        for i in range(10):
            state, metrics = step(
                state, _batches(i, cfg.num_learners, cfg.k_steps)
            )
        finals[donate] = (state, metrics)
    _bitwise(finals[False][0], finals[True][0])
    _bitwise(finals[False][1], finals[True][1])


def test_zc1_donation_parity_per_leaf_path():
    """Donation is orthogonal to packing: the legacy per-leaf state
    donates leaf-wise with the same bitwise guarantee."""
    cfg = dc.replace(_cfg("flat", "dense"), packed=False)
    finals = {}
    for donate in (False, True):
        state = init_state(PARAMS, cfg)
        step = make_jit_meta_step(mlp_loss, cfg, donate=donate)
        for i in range(10):
            state, _ = step(state, _batches(i, cfg.num_learners, cfg.k_steps))
        finals[donate] = state
    _bitwise(finals[False], finals[True])


# ---------------------------------------------------------------------------
# ZC2: the donation contract
# ---------------------------------------------------------------------------


def test_zc2_donated_input_is_dead():
    cfg = _cfg("flat", "dense")
    state = init_state(PARAMS, cfg)
    step = make_jit_meta_step(mlp_loss, cfg)  # cfg.donate defaults on
    new_state, _ = step(state, _batches(0, cfg.num_learners, cfg.k_steps))
    with pytest.raises((RuntimeError, ValueError), match="deleted|donated"):
        np.asarray(state.global_params)
    # the returned state is live and steps again
    new_state, _ = step(new_state, _batches(1, cfg.num_learners, cfg.k_steps))
    assert np.isfinite(np.asarray(new_state.global_params)).all()


def test_zc2_donate_gated_on_config():
    cfg = dc.replace(_cfg("flat", "dense"), donate=False)
    state = init_state(PARAMS, cfg)
    step = make_jit_meta_step(mlp_loss, cfg)
    step(state, _batches(0, cfg.num_learners, cfg.k_steps))
    # donate=False: the input state survives the call
    assert np.isfinite(np.asarray(state.global_params)).all()
    assert STATE_ARGNUM == 0


def test_zc2_trainer_checkpoints_returned_state(tmp_path):
    """The Trainer under donation: runs, checkpoints mid-run (off the
    returned state), and the checkpoint restores into a resumed run."""
    from repro.checkpoint import latest_checkpoint, load_state
    from repro.configs.base import TrainConfig
    from repro.core.trainer import Trainer

    mcfg = MAvgConfig(algorithm="mavg", num_learners=2, k_steps=2)
    assert mcfg.donate
    tcfg = TrainConfig(model=None, mavg=mcfg, meta_steps=4, log_every=10,
                       checkpoint_dir=str(tmp_path), checkpoint_every=2)

    def bf(rng, step):
        kx, ky = jax.random.split(rng)
        return {"x": jax.random.normal(kx, (2, 2, 4, D)),
                "y": jax.random.randint(ky, (2, 2, 4), 0, C)}

    tr = Trainer(tcfg, mlp_loss, lambda r: mlp_init(r, D, H, C), bf)
    hist = tr.run(log=None)
    assert len(hist) == 4
    path = latest_checkpoint(str(tmp_path))
    assert path is not None and path.endswith("step_00000004.npz")
    restored = load_state(path, jax.eval_shape(lambda: tr.state))
    _bitwise(restored, jax.tree.map(lambda x: x, tr.state))


# ---------------------------------------------------------------------------
# ZC3: fused momentum -> broadcast
# ---------------------------------------------------------------------------


def _wva(rows=24):
    return (jnp.asarray(RNG.randn(rows, 128), jnp.float32)
            for _ in range(3))


@pytest.mark.parametrize("nesterov", [False, True])
@pytest.mark.parametrize("ldtype", [jnp.float32, jnp.bfloat16])
def test_zc3_oracle_route_bitwise_vs_unfused(nesterov, ldtype):
    from repro.topology.base import block_momentum_update
    from repro.utils import tree_broadcast_learners, tree_cast

    w, v, a = _wva()
    L = 5

    def fused(w, v, a):
        return ops.fused_momentum_broadcast(
            w, v, a, mu=0.7, eta=0.9, num_learners=L, ldtype=ldtype,
            nesterov=nesterov, use_pallas=False,
        )

    def unfused(w, v, a):
        gp, vv = block_momentum_update(w, v, a, mu=0.7, eta=0.9,
                                       nesterov=nesterov)
        return gp, vv, tree_broadcast_learners(tree_cast(gp, ldtype), L)

    _bitwise(jax.jit(fused)(w, v, a), jax.jit(unfused)(w, v, a))


@pytest.mark.parametrize("nesterov", [False, True])
def test_zc3_kernel_matches_oracle(nesterov):
    w, v, a = _wva(rows=40)
    L = 3
    out_k = ops.fused_momentum_broadcast(
        w, v, a, mu=0.7, eta=1.3, num_learners=L, ldtype=jnp.bfloat16,
        nesterov=nesterov, use_pallas=True, interpret=True,
    )
    out_r = ref.fused_momentum_broadcast_ref(
        w, v, a, 0.7, 1.3, L, jnp.bfloat16, nesterov=nesterov
    )
    for x, y in zip(out_k, out_r):
        np.testing.assert_allclose(
            np.asarray(x, np.float32), np.asarray(y, np.float32),
            rtol=1e-5, atol=1e-6,
        )
    # shapes/dtypes: (rows,128) f32 x2 + (L, rows, 128) learner dtype
    assert out_k[2].shape == (L, 40, 128) and out_k[2].dtype == jnp.bfloat16


def test_zc3_learner_plane_is_cast_broadcast():
    """Within each route the emitted learner plane is EXACTLY the cast
    broadcast of that route's new meta params — no drift between what the
    meta plane holds and what the learners restart from."""
    w, v, a = _wva()
    for use_pallas in (False, True):
        gp2, _v2, lrn = ops.fused_momentum_broadcast(
            w, v, a, mu=0.6, eta=1.0, num_learners=4, ldtype=jnp.bfloat16,
            use_pallas=use_pallas, interpret=True,
        )
        want = np.broadcast_to(
            np.asarray(gp2.astype(jnp.bfloat16), np.float32)[None],
            (4, 24, 128),
        )
        np.testing.assert_array_equal(np.asarray(lrn, np.float32), want)


def test_zc3_flat_fused_trajectory_matches_pr4_path():
    """The FlatAllReduce wiring through the fused kernel keeps the packed
    dense trajectory bitwise on the per-leaf (PR 4 oracle) trajectory."""
    cfg = _cfg("flat", "dense")
    state_p = init_state(PARAMS, cfg)
    state_l = init_state(PARAMS, dc.replace(cfg, packed=False))
    step_p = jax.jit(make_meta_step(mlp_loss, cfg))
    step_l = jax.jit(make_meta_step(mlp_loss, dc.replace(cfg, packed=False)))
    for i in range(5):
        b = _batches(i, cfg.num_learners, cfg.k_steps)
        state_p, _ = step_p(state_p, b)
        state_l, _ = step_l(state_l, b)
    _bitwise(state_p.global_params, state_p.spec.pack(state_l.global_params))
    _bitwise(state_p.momentum, state_p.spec.pack(state_l.momentum))


# ---------------------------------------------------------------------------
# ZC4: compress-only kernel
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("use_pallas", [False, True])
@pytest.mark.parametrize("L,rows,block", [(2, 8, 8), (4, 64, None),
                                          (3, 24, 8)])
def test_zc4_compress_only_matches_pack_update_zero_gp(use_pallas, L, rows,
                                                       block):
    d = jnp.asarray(RNG.randn(L, rows, 128) * 0.05, jnp.float32)
    u = jnp.asarray(RNG.rand(L, rows, 128), jnp.float32)
    co = ops.pack_compress(d, u, block=block, use_pallas=use_pallas,
                           interpret=True)
    pu = ops.pack_update(d, jnp.zeros((rows, 128), jnp.float32), None, u,
                         block=block, use_pallas=use_pallas, interpret=True)
    for name, x, y in zip(("c", "err", "scales"), co, pu):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y), err_msg=name)
    # non-EF route: the err plane is never produced (with_err=False — a
    # pallas_call output can't be DCE'd), c/scales stay bitwise
    c2, err2, s2 = ops.pack_compress(d, u, block=block, with_err=False,
                                     use_pallas=use_pallas, interpret=True)
    assert err2 is None
    np.testing.assert_array_equal(np.asarray(c2), np.asarray(co[0]))
    np.testing.assert_array_equal(np.asarray(s2), np.asarray(co[2]))


def test_zc4_compress_residual_matches_two_pass():
    """QuantReducer._compress_residual (the err the kernel computed
    in-register) is bitwise the fallback _compress + tree_sub pass, on
    the packed plane and on a per-leaf pytree."""
    from repro.comm import QuantReducer
    from repro.utils import tree_sub

    red = QuantReducer(dtype="int8")
    step = jnp.int32(3)
    # packed plane
    delta = jnp.asarray(RNG.randn(4, 16, 128) * 0.1, jnp.float32)
    c1, wire1 = red._compress(delta, step)
    c2, err2, wire2 = red._compress_residual(delta, step)
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))
    np.testing.assert_array_equal(
        np.asarray(tree_sub(delta, c1)), np.asarray(err2)
    )
    assert wire1 == wire2
    # per-leaf pytree falls back to the generic two-pass route
    tree = {"a": jnp.asarray(RNG.randn(4, 37) * 0.1, jnp.float32),
            "b": jnp.asarray(RNG.randn(4, 5, 9) * 0.1, jnp.float32)}
    c3, err3, wire3 = red._compress_residual(tree, step)
    c4, wire4 = red._compress(tree, step)
    _bitwise(c3, c4)
    _bitwise(err3, tree_sub(tree, c3))
    assert wire3 == wire4


def test_zc4_gossip_ef_trajectory_matches_pr4_route():
    """The gossip int8+EF mix through the compress-only kernel stays
    bitwise on what the PR 4 route (pack_update with a synthesized zero
    gp plane + tree_sub residual) produced."""
    from repro.comm import ErrorFeedback, QuantReducer
    from repro.topology.gossip import compress_stack
    from repro.utils import tree_sub

    red = ErrorFeedback(QuantReducer(dtype="int8"))
    delta = jnp.asarray(RNG.randn(4, 16, 128) * 0.1, jnp.float32)
    res = jnp.asarray(RNG.randn(4, 16, 128) * 1e-3, jnp.float32)
    learners = jnp.asarray(RNG.randn(4, 16, 128), jnp.float32)
    step = jnp.int32(5)
    c, new_res, wire = compress_stack(red, delta, res, step=step,
                                      learners=learners)
    # PR 4 route, reproduced inline
    d_ef = delta + res
    c_old, wire_old = red.inner._compress(d_ef, step)
    np.testing.assert_array_equal(np.asarray(c), np.asarray(c_old))
    np.testing.assert_array_equal(
        np.asarray(new_res), np.asarray(tree_sub(d_ef, c_old))
    )
    assert wire == wire_old


# ---------------------------------------------------------------------------
# ZC5: memory and traffic on xlstm-350m's full-width meta planes
# ---------------------------------------------------------------------------

XL_L = 2  # the benchmark cell's learners, bf16 copies


def _xl_plane():
    """xlstm-350m's abstract f32 (rows, 128) meta plane and its bytes:
    nothing is allocated, the programs below are only traced or compiled."""
    from repro.configs.base import get_config
    from repro.launch.specs import abstract_params
    from repro.pack import make_pack_spec

    spec = make_pack_spec(abstract_params(get_config("xlstm-350m")))
    return (jax.ShapeDtypeStruct((spec.rows, 128), jnp.float32),
            spec.plane_bytes("float32"))


def _kernel_outputs_read_back(fn, *args):
    """Bytes of Pallas kernel outputs that later ops of ``fn``'s traced
    program read again. XLA cannot fuse across a pallas_call, so each is
    one more HBM read of a plane the kernel has just written."""
    written, nbytes = set(), 0
    for eqn in jax.make_jaxpr(fn)(*args).jaxpr.eqns:
        nbytes += sum(v.aval.size * v.aval.dtype.itemsize
                      for v in eqn.invars
                      if isinstance(v, jax.extend.core.Var) and v in written)
        if eqn.primitive.name == "pallas_call":
            written.update(eqn.outvars)
    return nbytes


def _donated_peak_state():
    """Donating the state planes to the flat dense meta mix (learner mean
    + fused momentum->broadcast) cuts the compiled program's peak state to
    <= 0.6x the functional program's, through real input/output aliases."""
    from repro.roofline.hlo_cost import jit_cost

    plane, _ = _xl_plane()
    lrn = jax.ShapeDtypeStruct((XL_L,) + plane.shape, jnp.bfloat16)

    def meta_mix(gp, v, lrn):
        a = jnp.mean(lrn.astype(jnp.float32), axis=0)
        return ref.fused_momentum_broadcast_ref(gp, v, a, 0.7, 1.0, XL_L,
                                                lrn.dtype)

    fun = jit_cost(meta_mix, plane, plane, lrn)
    don = jit_cost(meta_mix, plane, plane, lrn, donate_argnums=(0, 1, 2))
    assert don.alias_bytes > 0
    assert don.peak_state_bytes <= 0.6 * fun.peak_state_bytes, (don, fun)


def _fused_broadcast_read_removed():
    """The fused kernel emits the learner planes from the update itself;
    the unfused route reads the new meta plane back to broadcast it."""
    from repro.topology.base import block_momentum_update
    from repro.utils import tree_broadcast_learners, tree_cast

    plane, plane_b = _xl_plane()

    def unfused(w, v, a):
        gp, vv = block_momentum_update(w, v, a, mu=0.7, eta=1.0,
                                       use_pallas=True)
        return gp, vv, tree_broadcast_learners(tree_cast(gp, jnp.bfloat16),
                                               XL_L)

    def fused(w, v, a):
        return ops.fused_momentum_broadcast(
            w, v, a, mu=0.7, eta=1.0, num_learners=XL_L,
            ldtype=jnp.bfloat16, use_pallas=True)

    removed = (_kernel_outputs_read_back(unfused, plane, plane, plane)
               - _kernel_outputs_read_back(fused, plane, plane, plane))
    assert removed >= plane_b, removed / plane_b


def _compress_only_traffic_removed():
    """pack_compress drops the zero gp plane pack_update had to read: the
    compiled program moves >= 1 plane less."""
    from repro.roofline.hlo_cost import jit_cost

    plane, plane_b = _xl_plane()
    d = jax.ShapeDtypeStruct((XL_L,) + plane.shape, jnp.float32)
    zero_gp = jit_cost(
        lambda d, g, u: ops.pack_update(d, g, None, u, block=64,
                                        use_pallas=False), d, plane, d)
    compress = jit_cost(
        lambda d, u: ops.pack_compress(d, u, block=64, use_pallas=False),
        d, d)
    removed = zero_gp.hbm_bytes - compress.hbm_bytes
    assert removed >= plane_b, removed / plane_b


@pytest.mark.parametrize("check", [
    _donated_peak_state, _fused_broadcast_read_removed,
    _compress_only_traffic_removed,
], ids=lambda f: f.__name__.strip("_"))
def test_zc5_xlstm350m_meta_planes(check):
    check()
