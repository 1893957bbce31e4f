"""The sLSTM time loop as a Pallas kernel pair (kernels/slstm.py) against
the ``lax.scan`` path it replaces on TPU (models/xlstm.py ``_slstm_scan``).

On the CPU the kernels run in interpret mode with f32 products, as the
scan's dot is there, so the two paths differ only by f32 rounding in
another order: each compared leaf is held to 1e-4 of its largest
magnitude (at least 1).

  SK1  h of every step, the final (c, n, h, m), and the gradients with
       respect to the gate pre-activations, every r_g and the initial
       state agree: several time blocks, B = 1 and 4, a non-zero initial
       state (a continuation after a prefill), a large forget-gate bias
       where the stabiliser m decides, and the learner vmap.
  SK2  ``slstm_seq`` takes the kernel only for whole time blocks on a TPU
       (here described by patching the platform): the scan for S = 1
       (decode), for a length the time block does not divide, under a
       non-default matmul precision, and for work placed on the CPU of a
       TPU host by ``jax.default_device`` (the CPU references of
       chip_smoke.py and bench/calibrate.py).
  SK3  the xLSTM loss and its gradient through ``_scan_groups`` (one
       checkpoint per block) agree between the two paths.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels import ops
from repro.kernels import slstm as sl
from repro.models import xlstm

NH, HD = 2, 128
T = 8  # time block in the tests
TOL = 1e-4


def _inputs(seed, B, S, f_bias=0.0, i_scale=1.0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    pre = {g: jax.random.normal(ks[i], (B, S, NH, HD), jnp.float32)
           for i, g in enumerate("ifzo")}
    pre["i"] = pre["i"] * i_scale
    pre["f"] = pre["f"] + f_bias
    rs = {g: jax.random.normal(ks[4 + i], (NH, HD, HD), jnp.float32)
          / np.sqrt(HD) for i, g in enumerate("ifzo")}
    return pre, rs


def _zero_state(B):
    z = jnp.zeros((B, NH, HD), jnp.float32)
    return (z, z, z, jnp.full((B, NH, HD), -1e30, jnp.float32))


def _kernel_loop(pre, rs, state):
    """``_slstm_kernel`` at the tests' time block."""
    B, S, nh, hd = pre["i"].shape
    d = nh * hd
    g = jnp.stack([pre[k] for k in "ifzo"], axis=3)
    hs, st = ops.slstm_recurrence(
        g.transpose(1, 0, 2, 3, 4).reshape(S, B, 4 * d),
        [rs[k] for k in "ifzo"], [a.reshape(B, d) for a in state],
        time_block=T, interpret=True)
    return (hs.transpose(1, 0, 2).reshape(B, S, nh, hd),
            tuple(a.reshape(B, nh, hd) for a in st))


def _loss(loop, w):
    def f(pre, rs, state):
        hs, (c, n, h, m) = loop(pre, rs, state)
        return (jnp.sum(hs * w) + 0.3 * jnp.sum(c) + 0.2 * jnp.sum(n)
                + jnp.sum(h) + 0.1 * jnp.sum(m))
    return f


def _assert_close(got, want):
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(want),
                            jax.tree.leaves(got)):
        a, b = np.asarray(a), np.asarray(b)
        assert np.all(np.isfinite(b)), jax.tree_util.keystr(path)
        scale = max(float(np.max(np.abs(a))), 1.0)
        err = float(np.max(np.abs(a - b)))
        assert err <= TOL * scale, (jax.tree_util.keystr(path), err, scale)


def _state_after_prefill(pre, rs, B):
    """The scan's state after a 12-step prefill: c, n, h and m all away
    from the zero state."""
    head = {g: a[:, :12] for g, a in pre.items()}
    return xlstm._slstm_scan(head, rs, _zero_state(B))[1]


CASES = {
    # name: (B, S, f_bias, i_scale, continued)
    "four_blocks": (2, 4 * T, 0.0, 1.0, False),
    "b1": (1, 2 * T, 0.0, 1.0, False),
    "b4": (4, 2 * T, 0.0, 1.0, False),
    "continued_state": (2, 2 * T, 0.0, 1.0, True),
    "forget_bias": (2, 2 * T, 8.0, 4.0, False),
}


@pytest.mark.parametrize("case", list(CASES))
def test_sk1_kernel_matches_scan(case):
    B, S, f_bias, i_scale, continued = CASES[case]
    pre, rs = _inputs(1, B, S, f_bias, i_scale)
    state = (_state_after_prefill(_inputs(2, B, 12)[0], rs, B)
             if continued else _zero_state(B))
    w = jax.random.normal(jax.random.PRNGKey(3), (B, S, NH, HD))
    want = xlstm._slstm_scan(pre, rs, state)
    got = _kernel_loop(pre, rs, state)
    _assert_close(got, want)
    grad = lambda loop: jax.grad(_loss(loop, w), argnums=(0, 1, 2))(
        pre, rs, state)
    _assert_close(grad(_kernel_loop), grad(xlstm._slstm_scan))


def test_sk1_kernel_matches_scan_under_vmap():
    L, B, S = 2, 2, 2 * T
    pres, rss = zip(*(_inputs(10 + j, B, S) for j in range(L)))
    pre = jax.tree.map(lambda *a: jnp.stack(a), *pres)
    rs = jax.tree.map(lambda *a: jnp.stack(a), *rss)
    state = jax.tree.map(lambda a: jnp.broadcast_to(a, (L,) + a.shape),
                         _zero_state(B))
    w = jax.random.normal(jax.random.PRNGKey(4), (L, B, S, NH, HD))

    def grad(loop):
        f = lambda p, r, s, w: jax.grad(_loss(loop, w), argnums=(0, 1))(
            p, r, s)
        return jax.vmap(f)(pre, rs, state, w)

    _assert_close(jax.vmap(_kernel_loop)(pre, rs, state),
                  jax.vmap(xlstm._slstm_scan)(pre, rs, state))
    _assert_close(grad(_kernel_loop), grad(xlstm._slstm_scan))


# the model's own block at lane-aligned heads
CFG = dataclasses.replace(get_config("xlstm-350m").reduced(), num_layers=2,
                          d_model=NH * HD, num_heads=NH, num_kv_heads=NH,
                          head_dim=HD,
                          slstm_every=2, dtype="float32")


def _uses_kernel(S, B=2):
    bp = xlstm._init_slstm_block(jax.random.PRNGKey(0), CFG)
    x = jnp.zeros((B, S, CFG.d_model), jnp.float32)
    jaxpr = str(jax.make_jaxpr(lambda p, x: xlstm.slstm_seq(p, CFG, x))(
        bp, x))
    return "pallas_call" in jaxpr


@pytest.mark.parametrize("S,kernel", [(1, False), (sl.TIME_BLOCK + 8, False),
                                      (2 * sl.TIME_BLOCK, True)])
def test_sk2_dispatch_by_shape(monkeypatch, S, kernel):
    assert not _uses_kernel(2 * sl.TIME_BLOCK)  # the CPU: always the scan
    monkeypatch.setattr(ops, "_platform", lambda: "tpu")
    assert _uses_kernel(S) is kernel


def test_sk2_non_default_precision_takes_the_scan(monkeypatch):
    monkeypatch.setattr(ops, "_platform", lambda: "tpu")
    assert _uses_kernel(2 * sl.TIME_BLOCK)
    with jax.default_matmul_precision("highest"):
        assert not _uses_kernel(2 * sl.TIME_BLOCK)


def test_sk2_cpu_device_on_a_tpu_host_takes_the_scan(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ops.resolve_use_pallas(None)
    assert _uses_kernel(2 * sl.TIME_BLOCK)
    with jax.default_device(jax.devices("cpu")[0]):
        assert not ops.resolve_use_pallas(None)
        assert not _uses_kernel(2 * sl.TIME_BLOCK)


def test_sk3_loss_and_grad_through_the_block_stack(monkeypatch):
    B, S = 2, 2 * sl.TIME_BLOCK
    params = xlstm.init(jax.random.PRNGKey(0), CFG)
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                              CFG.vocab_size, jnp.int32)
    batch = {"tokens": toks, "labels": toks}
    vg = lambda: jax.value_and_grad(
        lambda p: xlstm.loss_fn(p, CFG, batch)[0])(params)
    want = vg()
    # the kernel path, in interpret mode on the CPU
    monkeypatch.setattr(ops, "slstm_kernel_applies",
                        lambda S, B, d, hd: sl.fits(S, B, d, hd))
    got = vg()
    _assert_close(got, want)
