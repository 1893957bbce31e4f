"""repro.robust acceptance tests (DESIGN.md §14).

The contract, pinned here:

  R1  robust-reduce kernel: trim=0 is bitwise the plain mean; the Pallas
      kernel (interpret) agrees bit-for-bit with the jnp oracle for every
      trim; median_trim resolves the coordinate-wise median.
  R2  plumbing no-op: RobustConfig(estimator='mean', clip off, score off)
      is bitwise identical to robust=None on every topology — the robust
      hooks themselves never perturb a run they don't act on.
  R3  the trimmed mean bounds a corrupt learner: one poisoned learner
      moves the plain-mean consensus by O(magnitude / L) while the
      trimmed consensus stays within the benign spread.
  R4  rejection, not deferral: a clipped mix is bitwise identical —
      global params AND error-feedback residual — to a robust-off mix fed
      the pre-clipped learner stack, so the clipped-away mass never
      enters the EF residual and is never replayed.
  R5  the trailing-median clip budget: no clipping during warmup, the
      over-budget learner (and only it) is clipped after, and the
      unclipped learners pass through bit-identical.
  R6  Krum-style anomaly scores single out the corrupted learner.
  R7  the ring state rides MetaState.topo through jit on every clipping
      topology, and the full robust stack runs end to end on all four.
  R8  robust telemetry: Trainer repackages the robust_* metrics into
      schema-v4 ``robust`` records, step rows stay on the step schema,
      and tools/check_telemetry.py validates the stream.
  R9  inline quarantine: a persistently-anomalous learner is masked out
      of the elastic membership mid-run — no HealthHalt, no rollback —
      and the run completes its target steps.
  R10 finite faults (chaos): finite_scale / finite_bitflip corrupt the
      payload with values the finite guard CANNOT see (nonfinite_learners
      stays 0) — the threat model repro.robust exists for.
  R11 config validation: impossible trims and flat-topology quarantine
      are rejected up front.
  R12 benchmarks/robust_bench.py at smoke size: under sticky finite
      corruption the robust mix lands within 5% of fault-free loss with a
      finite state and no rollback, while the plain mean degrades.
"""
import dataclasses as dc
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.chaos import ChaosConfig, FaultSchedule, FaultSpec, PayloadCorruptor
from repro.configs.base import (
    AsyncConfig,
    CommConfig,
    ElasticConfig,
    MAvgConfig,
    ObsConfig,
    RobustConfig,
    TopologyConfig,
    TrainConfig,
)
from repro.core import Trainer
from repro.core.meta import init_state, make_meta_step
from repro.data import classif_batch_fn
from repro.kernels import ops
from repro.kernels.robust_reduce import median_trim, robust_reduce_3d
from repro.models.simple import mlp_init, mlp_loss
from repro.robust import (
    RobustAggregator,
    anomaly_scores,
    make_robust,
    robust_ring_buffers,
)
from repro.topology import make_topology

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

D, C, H = 8, 4, 16
PARAMS = mlp_init(jax.random.PRNGKey(0), D, H, C)


def _batches(seed, L, K, B=4):
    kx, ky = jax.random.split(jax.random.PRNGKey(seed))
    return {
        "x": jax.random.normal(kx, (L, K, B, D)),
        "y": jax.random.randint(ky, (L, K, B), 0, C),
    }


def _leaves_equal(a, b):
    return all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))
    )


def _run(cfg, n_steps=3, params=PARAMS):
    state = init_state(params, cfg)
    step = jax.jit(make_meta_step(mlp_loss, cfg))
    metrics = {}
    for i in range(n_steps):
        state, metrics = step(
            state, _batches(i, cfg.num_learners, cfg.k_steps)
        )
    return state, metrics


# ---------------------------------------------------------------------------
# R1: kernel parity
# ---------------------------------------------------------------------------


def test_r1_trim0_is_bitwise_mean():
    x = jax.random.normal(jax.random.PRNGKey(1), (5, 16, 128), jnp.float32)
    out = robust_reduce_3d(x, trim=0, interpret=True)
    assert np.array_equal(np.asarray(out), np.asarray(jnp.mean(x, axis=0)))
    # the ops router takes the same kernel path for a packed-shaped stack
    out2 = ops.robust_reduce(x, trim=0, use_pallas=True, interpret=True)
    assert np.array_equal(np.asarray(out), np.asarray(out2))


@pytest.mark.parametrize("trim", [0, 1, 2])
def test_r1_kernel_matches_oracle(trim):
    from repro.kernels import ref

    x = jax.random.normal(jax.random.PRNGKey(2), (6, 16, 128), jnp.float32)
    k = robust_reduce_3d(x, trim=trim, interpret=True)
    # compare under jit — the only way either runs in production; the
    # EAGER oracle may reassociate the L-sum differently for odd L
    r = jax.jit(lambda y: ref.robust_reduce_ref(y, trim))(x)
    assert np.array_equal(np.asarray(k), np.asarray(r))


def test_r1_median_trim_is_the_median():
    assert median_trim(5) == 2 and median_trim(4) == 1 and median_trim(2) == 0
    for L in (5, 6):
        x = jax.random.normal(jax.random.PRNGKey(3), (L, 8, 128), jnp.float32)
        m = robust_reduce_3d(x, trim=median_trim(L), interpret=True)
        np.testing.assert_allclose(
            np.asarray(m), np.median(np.asarray(x), axis=0), atol=1e-6
        )


# ---------------------------------------------------------------------------
# R2: inert robust config == robust=None, bitwise, on every topology
# ---------------------------------------------------------------------------

_INERT = RobustConfig(estimator="mean", clip_mult=0.0, score=False)

_TOPOS = {
    "flat": {},
    "hier": dict(topology=TopologyConfig(kind="hierarchical", groups=2)),
    "gossip": dict(topology=TopologyConfig(kind="gossip", graph="ring")),
    "async": dict(topology=TopologyConfig(
        kind="async", server=AsyncConfig(staleness=2))),
}


@pytest.mark.parametrize("kind", sorted(_TOPOS))
def test_r2_inert_robust_is_bitwise_off(kind):
    base = dict(algorithm="mavg", num_learners=4, k_steps=2,
                learner_lr=0.1, momentum=0.6, **_TOPOS[kind])
    s_off, _ = _run(MAvgConfig(**base))
    s_on, m_on = _run(MAvgConfig(**base, robust=_INERT))
    assert _leaves_equal(s_off.global_params, s_on.global_params)
    assert _leaves_equal(s_off.momentum, s_on.momentum)
    assert _leaves_equal(s_off.learners, s_on.learners)
    assert not any(k.startswith("robust_clip") for k in m_on)


def test_r2_inert_robust_is_bitwise_off_unpacked():
    base = dict(algorithm="mavg", num_learners=4, k_steps=2,
                learner_lr=0.1, momentum=0.6, packed=False)
    s_off, _ = _run(MAvgConfig(**base))
    s_on, _ = _run(MAvgConfig(**base, robust=_INERT))
    assert _leaves_equal(s_off.global_params, s_on.global_params)
    assert _leaves_equal(s_off.learners, s_on.learners)


# ---------------------------------------------------------------------------
# R3: the trimmed mean bounds a corrupt learner
# ---------------------------------------------------------------------------


def _flat_mix_once(cfg, learners, gp, v, res, topo):
    topo_obj = make_topology(cfg)
    return topo_obj.mix(learners, gp, v, res, topo, step=0)


def test_r3_trimmed_bounds_corrupt_learner():
    L = 6
    base = dict(algorithm="mavg", num_learners=L, k_steps=2,
                learner_lr=0.1, momentum=0.0)
    cfg_mean = MAvgConfig(**base)
    cfg_trim = MAvgConfig(**base, robust=RobustConfig(
        estimator="trimmed", trim=1, score=False))
    state = init_state(PARAMS, cfg_mean)
    gp, v = state.global_params, state.momentum
    noise = jax.tree.map(
        lambda w: w + 1e-3 * jax.random.normal(
            jax.random.PRNGKey(4), w.shape, jnp.float32).astype(w.dtype),
        state.learners,
    )
    poisoned = jax.tree.map(lambda w: w.at[0].add(1e6), noise)

    def gp_after(cfg, learners):
        res = make_topology(cfg).init_buffers(gp, cfg)[0]
        out, *_ = _flat_mix_once(cfg, learners, gp, v, res, None)
        return out

    def dist(a, b):
        return float(sum(
            jnp.sum(jnp.square(x.astype(jnp.float32)
                               - y.astype(jnp.float32)))
            for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))
        )) ** 0.5

    clean_mean = gp_after(cfg_mean, noise)
    clean_trim = gp_after(cfg_trim, noise)
    dirty_mean = gp_after(cfg_mean, poisoned)
    dirty_trim = gp_after(cfg_trim, poisoned)
    # the plain mean swallows magnitude/L of the poison ...
    assert dist(dirty_mean, clean_mean) > 1e4
    # ... the trimmed mean stays within the benign noise spread
    assert dist(dirty_trim, clean_trim) < 1.0


# ---------------------------------------------------------------------------
# R4: clip rejection — the EF residual never sees the clipped-away mass
# ---------------------------------------------------------------------------


def test_r4_clip_is_rejection_not_deferral():
    base = dict(algorithm="mavg", num_learners=4, k_steps=2,
                learner_lr=0.1, momentum=0.6,
                comm=CommConfig(scheme="int8", error_feedback=True))
    rcfg = RobustConfig(estimator="mean", clip_mult=1.5, clip_window=1,
                        score=False)
    cfg_a = MAvgConfig(**base, robust=rcfg)
    cfg_b = MAvgConfig(**base)
    topo_a, topo_b = make_topology(cfg_a), make_topology(cfg_b)
    state = init_state(PARAMS, cfg_a, topology=topo_a)
    gp, v = state.global_params, state.momentum
    res_a = state.comm_residual
    res_b = topo_b.init_buffers(gp, cfg_b)[0]
    ring = {k: state.topo[k] for k in ("robust_ring", "robust_count")}

    benign = jax.tree.map(
        lambda w: w + 0.01 * jax.random.normal(
            jax.random.PRNGKey(5), w.shape, jnp.float32).astype(w.dtype),
        state.learners,
    )
    # warmup mix (ring not yet full): both sides must agree bitwise
    gp_a, v_a, _, res_a, ring, m_a = topo_a.mix(
        benign, gp, v, res_a, ring, step=0)
    gp_b, v_b, _, res_b, _, _ = topo_b.mix(
        benign, gp, v, res_b, None, step=0)
    assert float(m_a["robust_clipped_learners"]) == 0.0
    assert _leaves_equal(gp_a, gp_b) and _leaves_equal(res_a, res_b)

    # learner 3 blows up; the clip fires on side A
    corrupt = jax.tree.map(lambda w: w.at[3].add(50.0), benign)
    gp_a2, _, _, res_a2, ring2, m_a2 = topo_a.mix(
        corrupt, gp_a, v_a, res_a, ring, step=1)
    assert float(m_a2["robust_clipped_learners"]) == 1.0
    assert int(ring2["robust_count"]) == 2

    # side B (no robust) fed the PRE-CLIPPED stack lands on the same
    # global params AND the same EF residual, bit for bit — the clipped
    # -away mass was rejected before the compressor, not deferred into
    # the residual for replay
    clipped, _, _ = topo_a.robust.clip_learners(corrupt, gp_a, dict(ring))
    gp_b2, _, _, res_b2, _, _ = topo_b.mix(
        clipped, gp_b, v_b, res_b, None, step=1)
    assert _leaves_equal(gp_a2, gp_b2)
    assert _leaves_equal(res_a2, res_b2)


# ---------------------------------------------------------------------------
# R5: trailing-median clip budget (warmup, firing, bit-identity)
# ---------------------------------------------------------------------------


def test_r5_clip_budget_warmup_then_fires():
    rcfg = RobustConfig(estimator="mean", clip_mult=2.0, clip_window=2,
                        score=False)
    cfg = MAvgConfig(algorithm="mavg", num_learners=4, k_steps=2,
                     learner_lr=0.1, robust=rcfg)
    ra = make_robust(cfg)
    assert isinstance(ra, RobustAggregator) and ra.has_clip
    gp = {"w": jnp.zeros((32,), jnp.float32)}
    ben = {"w": 0.1 * jax.random.normal(jax.random.PRNGKey(6), (4, 32))}
    big = {"w": ben["w"].at[0].add(1000.0)}

    # warmup: even a blown-up learner passes through untouched
    topo = robust_ring_buffers(rcfg)
    out, _, m = ra.clip_learners(big, gp, topo)
    assert float(m["robust_clipped_learners"]) == 0.0
    assert _leaves_equal(out, big)

    # fill the ring with benign steps, then the budget fires
    topo = robust_ring_buffers(rcfg)
    for _ in range(rcfg.clip_window):
        _, topo, _ = ra.clip_learners(ben, gp, topo)
    out, _, m = ra.clip_learners(big, gp, topo)
    assert float(m["robust_clipped_learners"]) == 1.0
    budget = float(m["robust_clip_budget"])
    assert budget > 0.0
    clipped_norm = float(jnp.linalg.norm(out["w"][0]))
    assert clipped_norm <= budget * (1 + 1e-5)
    # the unclipped learners are bit-identical, not merely close
    assert np.array_equal(np.asarray(out["w"][1:]), np.asarray(big["w"][1:]))


# ---------------------------------------------------------------------------
# R6: anomaly scores
# ---------------------------------------------------------------------------


def test_r6_anomaly_score_singles_out_corrupt_learner():
    delta = {"w": 0.1 * jax.random.normal(jax.random.PRNGKey(7), (6, 64))}
    delta = jax.tree.map(lambda d: d.at[2].add(50.0), delta)
    s = np.asarray(anomaly_scores(delta))
    assert s.shape == (6,)
    assert int(np.argmax(s)) == 2
    peers = np.delete(s, 2)
    assert s[2] > 10.0 * peers.max()


# ---------------------------------------------------------------------------
# R7: ring rides MetaState.topo under jit; full stack on every topology
# ---------------------------------------------------------------------------

_FULL = RobustConfig(estimator="trimmed", trim=1, clip_mult=3.0,
                     clip_window=2, score=True)


@pytest.mark.parametrize("kind", sorted(_TOPOS))
def test_r7_full_robust_stack_end_to_end(kind):
    base = dict(algorithm="mavg", num_learners=4, k_steps=2,
                learner_lr=0.1, momentum=0.6, **_TOPOS[kind])
    # width 2 per hierarchical group cannot trim — the estimator stays
    # 'mean' there; the clip + scores are the robust leg under test
    rcfg = (dc.replace(_FULL, estimator="mean")
            if kind == "hier" else _FULL)
    cfg = MAvgConfig(**base, robust=rcfg)
    state = init_state(PARAMS, cfg)
    assert state.topo["robust_ring"].shape == (rcfg.clip_window,)
    step = jax.jit(make_meta_step(mlp_loss, cfg))
    for i in range(3):
        state, metrics = step(state, _batches(i, 4, 2))
    assert int(state.topo["robust_count"]) == 3
    assert float(np.asarray(state.topo["robust_ring"]).max()) > 0.0
    assert "robust_anomaly_score" in metrics
    assert "robust_clipped_learners" in metrics
    for x in jax.tree.leaves((state.global_params, state.learners)):
        assert np.isfinite(np.asarray(x)).all()


# ---------------------------------------------------------------------------
# R8: trainer telemetry — robust records, schema v4
# ---------------------------------------------------------------------------


def _check_telemetry():
    spec = importlib.util.spec_from_file_location(
        "check_telemetry", os.path.join(_ROOT, "tools", "check_telemetry.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_r8_robust_records_stream_schema_valid(tmp_path):
    L, K, B = 4, 2, 4
    mcfg = MAvgConfig(algorithm="mavg", num_learners=L, k_steps=K,
                      learner_lr=0.1, momentum=0.6, robust=_FULL)
    run_dir = str(tmp_path / "run")
    tcfg = TrainConfig(
        model=None, mavg=mcfg, batch_per_learner=B, meta_steps=4, seed=0,
        log_every=1, obs=ObsConfig(sink="jsonl", run_dir=run_dir),
    )
    trainer = Trainer(
        tcfg, mlp_loss,
        init_params_fn=lambda rng: mlp_init(rng, D, H, C),
        batch_fn=classif_batch_fn(D, C, L, K, B),
    )
    trainer.run(log=None)
    trainer.close()

    assert len(trainer.robust_records) == 4
    for rb in trainer.robust_records:
        assert rb["kind"] == "robust"
        for k in ("meta_step", "clipped_learners", "trim_fraction",
                  "anomaly_score"):
            assert k in rb
        assert len(rb["scores"]) == L
    assert trainer.robust_records[0]["trim_fraction"] == pytest.approx(0.5)
    # the robust_* scalars were POPPED out of the step rows
    for rec in trainer.history:
        assert not any(k.startswith("robust_") for k in rec)

    ct = _check_telemetry()
    schema = ct.load_schema(
        os.path.join(_ROOT, "tools", "telemetry_schema.json"))
    with open(os.path.join(run_dir, "run.jsonl")) as f:
        lines = list(f)
    assert ct.check_stream(lines, schema) == []
    import json

    kinds = [json.loads(ln)["kind"] for ln in lines if ln.strip()]
    assert kinds.count("robust") == 4


# ---------------------------------------------------------------------------
# R9: inline quarantine — graceful degradation without a rollback
# ---------------------------------------------------------------------------


def test_r9_inline_quarantine_masks_anomalous_learner(tmp_path):
    L, K, B, steps = 4, 2, 4, 6
    rcfg = RobustConfig(estimator="mean", score=True, quarantine_after=2,
                        score_ratio=4.0)
    mcfg = MAvgConfig(
        algorithm="mavg", num_learners=L, k_steps=K, learner_lr=0.05,
        momentum=0.6, robust=rcfg,
        topology=TopologyConfig(
            kind="gossip", graph="ring",
            elastic=ElasticConfig(period=steps, drop_frac=0.0)),
    )
    # sticky finite corruption: learner 3's payload is scaled x100 every
    # step — huge but finite, invisible to the finite guard
    chaos = ChaosConfig(seed=0, horizon=steps, faults=(
        FaultSpec("finite_scale", step=0, learner=3, duration=steps,
                  magnitude=100.0, sticky=True),
    ))
    tcfg = TrainConfig(
        model=None, mavg=mcfg, batch_per_learner=B, meta_steps=steps,
        seed=0, log_every=1, chaos=chaos, obs=ObsConfig(sink="none"),
    )
    trainer = Trainer(
        tcfg, mlp_loss,
        init_params_fn=lambda rng: mlp_init(rng, D, H, C),
        batch_fn=classif_batch_fn(D, C, L, K, B),
    )
    history = trainer.run(log=None)
    trainer.close()

    # the run COMPLETED — no HealthHalt, no supervisor, no rollback —
    # and the anomalous learner was quarantined inline after 2 windows
    assert len(history) == steps
    assert 3 in trainer.quarantined
    assert trainer.quarantined[3] <= 2
    m = np.asarray(trainer.state.topo["membership"])
    assert (m[:, 3] == 0.0).all()
    assert (m[:, :3] == 1.0).all()
    assert (m.sum(axis=1) >= 1.0).all()
    quarantined_rows = [
        rb for rb in trainer.robust_records if "quarantined" in rb
    ]
    assert quarantined_rows and quarantined_rows[0]["quarantined"] == [3]


# ---------------------------------------------------------------------------
# R10: finite chaos faults — the finite guard cannot see them
# ---------------------------------------------------------------------------


def test_r10_finite_fault_validation():
    with pytest.raises(AssertionError):
        FaultSpec("finite_scale", step=0, learner=0,
                  magnitude=float("inf"))
    with pytest.raises(AssertionError):
        FaultSpec("finite_scale", step=0, learner=0, magnitude=0.0)
    with pytest.raises(AssertionError):
        FaultSpec("finite_scale", step=0, learner=0, magnitude=2.0 ** 41)
    # the exponent-top bit is masked: flipping it would create the
    # inf/NaN the finite guard DOES catch, which defeats the point
    f = FaultSpec("finite_bitflip", step=0, learner=0, bit=31)
    assert f.bit == 29


@pytest.mark.parametrize("fault", [
    FaultSpec("finite_scale", step=0, learner=1, magnitude=64.0),
    FaultSpec("finite_bitflip", step=0, learner=1, bit=29),
])
def test_r10_finite_guard_is_blind_to_finite_corruption(fault):
    L, K = 2, 2
    mcfg = MAvgConfig(algorithm="mavg", num_learners=L, k_steps=K,
                      learner_lr=0.1, momentum=0.6, finite_guard=True)
    cor = PayloadCorruptor(
        FaultSchedule(ChaosConfig(seed=0, horizon=4, faults=(fault,)), L))
    assert cor.active
    plain = jax.jit(make_meta_step(mlp_loss, mcfg))
    dirty = jax.jit(make_meta_step(mlp_loss, mcfg, chaos=cor))
    s0 = init_state(PARAMS, mcfg)
    sp, _ = plain(s0, _batches(0, L, K))
    sd, md = dirty(s0, _batches(0, L, K))
    # the corruption LANDED (trajectory changed) and stayed finite, so
    # the finite guard saw nothing — zero learners reset
    assert not _leaves_equal(sp.global_params, sd.global_params)
    assert float(md["nonfinite_learners"]) == 0.0
    for x in jax.tree.leaves((sd.global_params, sd.learners)):
        assert np.isfinite(np.asarray(x)).all()


# ---------------------------------------------------------------------------
# R11: config validation
# ---------------------------------------------------------------------------


def test_r11_config_validation():
    with pytest.raises(ValueError, match="trim"):
        MAvgConfig(algorithm="mavg", num_learners=4, k_steps=2,
                   robust=RobustConfig(estimator="trimmed", trim=2))
    with pytest.raises(ValueError, match="trim"):
        MAvgConfig(algorithm="mavg", num_learners=8, k_steps=2,
                   topology=TopologyConfig(kind="hierarchical", groups=2),
                   robust=RobustConfig(estimator="trimmed", trim=2))
    with pytest.raises(ValueError, match="quarantine"):
        MAvgConfig(algorithm="mavg", num_learners=4, k_steps=2,
                   robust=RobustConfig(quarantine_after=2))
    with pytest.raises(AssertionError):
        RobustConfig(estimator="mode")
    with pytest.raises(AssertionError):
        RobustConfig(score_ratio=1.0)
    # the degenerate estimator is valid and inert
    assert make_robust(
        MAvgConfig(algorithm="mavg", num_learners=4, k_steps=2)
    ) is None


# ---------------------------------------------------------------------------
# R12: the robust suite's acceptance experiment (benchmarks/robust_bench.py)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def robust_accept(run_bench):
    (row,) = [r for r in run_bench("robust_bench")
              if r["kind"] == "robust_accept"]
    return row


@pytest.mark.parametrize("field,lo,hi", [
    # robust mix within 5% of fault-free under sticky finite corruption
    ("loss_vs_fault_free", None, 1.05),
    # the plain mean degrades under the same corruption: the threat is real
    ("mean_degrades", True, None),
    # graceful degradation: no supervisor rollback
    ("rollbacks", None, 0),
    ("state_finite", True, None),
], ids=["loss_vs_fault_free", "mean_degrades", "rollbacks",
        "state_finite"])
def test_r12_bench_acceptance(robust_accept, field, lo, hi):
    value = robust_accept[field]
    assert lo is None or value >= lo, (field, value)
    assert hi is None or value <= hi, (field, value)
