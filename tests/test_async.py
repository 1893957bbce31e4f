"""Async bounded-staleness meta server acceptance tests (DESIGN.md §12).

Invariants:
  A1  tau=0 (uniform all-ones profile) async == synchronous FlatAllReduce
      *bit-for-bit* — packed and per-leaf (the PK3-style parity pin that
      makes the synchronizer refactor a provable no-op for sync runs).
  A2  applied staleness never exceeds the configured bound tau, including
      the de-phased startup window.
  A3  the clock schedule is deterministic and checkpoint-resumable: a run
      halted mid-staleness-window continues bit-identically (the topo
      roundtrip itself lives in test_checkpoint).
  A4  downpour alias: center frozen for the legacy warmup window, stale
      displacements applied at full weight (decay 1.0) afterwards.
  A5  elastic membership composes: an absent learner cannot fire — drop
      is just unbounded lag on the same clock axis.
  A6  config validation: profiles that cannot honor the staleness bound,
      non-dense comm, and length mismatches are rejected eagerly.
  A7  work_completed matches the on-device fired_count accounting.
  A8  benchmarks/async_bench.py at smoke size: at 4x skew async lands
      within 5% of sync loss at equal samples, the barrier idles >= 40%,
      async takes >= 1.5x fewer model ticks, and the skewed profile's
      modeled wire stays at its recorded bytes.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import (
    AsyncConfig,
    CommConfig,
    ElasticConfig,
    MAvgConfig,
    TopologyConfig,
)
from repro.core.meta import init_state, make_meta_step
from repro.models.simple import mlp_init, mlp_loss
from repro.topology import make_topology, step_time_profile

D, C, H = 8, 4, 16
PARAMS = mlp_init(jax.random.PRNGKey(0), D, H, C)


def _batches(seed, L, K, B=4):
    kx, ky = jax.random.split(jax.random.PRNGKey(seed))
    return {"x": jax.random.normal(kx, (L, K, B, D)),
            "y": jax.random.randint(ky, (L, K, B), 0, C)}


def _run(cfg, n_steps=4, params=PARAMS):
    state = init_state(params, cfg)
    step = jax.jit(make_meta_step(mlp_loss, cfg))
    metrics = []
    for i in range(n_steps):
        state, m = step(state, _batches(i, cfg.num_learners, cfg.k_steps))
        metrics.append(m)
    return state, metrics


def _bitwise(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# A1: tau=0 degenerate case == synchronous flat, bitwise
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("packed", [True, False])
def test_a1_uniform_async_is_flat_bitwise(packed):
    base = dict(algorithm="mavg", num_learners=4, k_steps=3,
                learner_lr=0.1, momentum=0.6, packed=packed)
    s_flat, m_flat = _run(MAvgConfig(**base))
    s_async, m_async = _run(MAvgConfig(
        **base, topology=TopologyConfig(kind="async", server=AsyncConfig())))
    _bitwise(s_flat.global_params, s_async.global_params)
    _bitwise(s_flat.momentum, s_async.momentum)
    _bitwise(s_flat.learners, s_async.learners)
    np.testing.assert_array_equal(
        np.asarray(m_flat[-1]["loss"]), np.asarray(m_async[-1]["loss"]))
    # the degenerate case still reports the async bookkeeping
    assert float(m_async[-1]["staleness_max"]) == 0.0
    assert float(m_async[-1]["fired_count"]) == 4.0


def test_a1_eamsgd_alias_matches_legacy_update():
    """eamsgd routed through the async server (uniform profile, elastic
    update) applies the closed-form EASGD step: v' = mu v + alpha
    sum_j (w_j - w~); w~' = w~ + v'; learners relax by alpha toward w~'."""
    cfg = MAvgConfig(algorithm="eamsgd", num_learners=2, k_steps=2,
                     learner_lr=0.1, momentum=0.5, elastic_alpha=0.1)
    state = init_state(PARAMS, cfg)
    step = jax.jit(make_meta_step(mlp_loss, cfg))
    prev = state
    state, _ = step(state, _batches(0, 2, 2))
    # reconstruct from the previous state's learners after one local phase
    # is circular; instead pin the update identity on the second step
    # using the recorded state: w~' - w~ == v'
    prev = state
    state, _ = step(state, _batches(1, 2, 2))
    dw = jax.tree.map(lambda a, b: np.asarray(a) - np.asarray(b),
                      state.global_params, prev.global_params)
    for d, v in zip(jax.tree.leaves(dw), jax.tree.leaves(state.momentum)):
        np.testing.assert_allclose(d, np.asarray(v), rtol=1e-6, atol=1e-7)


# ---------------------------------------------------------------------------
# A2: bounded staleness, including the startup window
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("profile,tau", [((1, 1, 2, 4), 3),
                                         ((1, 3, 3, 5), 4)])
def test_a2_applied_staleness_bounded(profile, tau):
    cfg = MAvgConfig(algorithm="mavg", num_learners=4, k_steps=2,
                     momentum=0.5,
                     topology=TopologyConfig(kind="async", server=AsyncConfig(
                         staleness=tau, step_time=profile)))
    _, metrics = _run(cfg, n_steps=3 * max(profile) + 2)
    worst = max(float(m["staleness_max"]) for m in metrics)
    assert worst <= tau, (worst, tau)
    # the skewed profile does produce *some* staleness
    assert any(float(m["staleness_max"]) > 0 for m in metrics)


# ---------------------------------------------------------------------------
# A3: deterministic trajectory across a halt/resume boundary
# ---------------------------------------------------------------------------


def test_a3_resume_mid_window_identical_trajectory():
    cfg = MAvgConfig(algorithm="mavg", num_learners=4, k_steps=2,
                     momentum=0.5,
                     topology=TopologyConfig(kind="async", server=AsyncConfig(
                         staleness=3, step_time=(1, 2, 3, 4))))
    step = jax.jit(make_meta_step(mlp_loss, cfg))
    live = init_state(PARAMS, cfg)
    for i in range(7):
        live, _ = step(live, _batches(i, 4, 2))
    # replay from scratch with an identical schedule: same trajectory —
    # the clocks are state, not host-side mutable context
    replay = init_state(PARAMS, cfg)
    for i in range(7):
        replay, _ = step(replay, _batches(i, 4, 2))
    _bitwise(live, replay)


# ---------------------------------------------------------------------------
# A4: downpour alias regression (legacy warmup + stale application)
# ---------------------------------------------------------------------------


def test_a4_downpour_alias_warmup_and_stale_norm():
    cfg = MAvgConfig(algorithm="downpour", num_learners=2, k_steps=2,
                     learner_lr=0.1, staleness=3)
    spec_params = init_state(PARAMS, cfg).global_params
    state = init_state(PARAMS, cfg)
    step = jax.jit(make_meta_step(mlp_loss, cfg))
    moved = []
    for i in range(6):
        state, m = step(state, _batches(i, 2, 2))
        delta = max(float(jnp.abs(a - b).max()) for a, b in zip(
            jax.tree.leaves(state.global_params),
            jax.tree.leaves(spec_params)))
        moved.append(delta > 1e-7)
        assert "stale_norm" in m  # legacy metric name flows on
    # frozen through the warmup window, moving afterwards
    assert not any(moved[:3]) and all(moved[3:])


# ---------------------------------------------------------------------------
# A5: elastic membership composes (drop = lag on the same axis)
# ---------------------------------------------------------------------------


def test_a5_absent_learner_never_fires():
    cfg = MAvgConfig(
        algorithm="mavg", num_learners=4, k_steps=2, momentum=0.5,
        topology=TopologyConfig(
            kind="async",
            server=AsyncConfig(staleness=2, step_time=(1, 1, 2, 2)),
            elastic=ElasticConfig(period=3, drop_frac=0.25, seed=1)))
    topo = make_topology(cfg)
    state = init_state(PARAMS, cfg)
    step = jax.jit(make_meta_step(mlp_loss, cfg))
    sched = np.asarray(state.topo["membership"])
    for i in range(9):
        fire = np.asarray(topo.fire_mask(state.topo, jnp.int32(i)))
        absent = sched[i % 3] == 0
        assert not (fire & absent).any()
        prev = state
        state, _ = step(state, _batches(i, 4, 2))
        # absent learners are fully frozen this tick
        for a, b in zip(jax.tree.leaves(prev.learners),
                        jax.tree.leaves(state.learners)):
            np.testing.assert_array_equal(
                np.asarray(a)[absent], np.asarray(b)[absent])


# ---------------------------------------------------------------------------
# A6: eager config validation
# ---------------------------------------------------------------------------


def test_a6_validation():
    # a 5-tick straggler cannot honor a tau=2 bound
    with pytest.raises(ValueError, match="staleness"):
        AsyncConfig(staleness=2, step_time=(1, 1, 5))
    # the async server ships dense displacement planes
    with pytest.raises(ValueError, match="dense"):
        MAvgConfig(num_learners=2, k_steps=2,
                   comm=CommConfig(scheme="int8"),
                   topology=TopologyConfig(kind="async"))
    # profile length must match the learner count
    with pytest.raises(ValueError, match="step_time"):
        MAvgConfig(num_learners=4, k_steps=2,
                   topology=TopologyConfig(kind="async", server=AsyncConfig(
                       staleness=1, step_time=(1, 2))))
    # seeded skew profile: deterministic, spans 1..skew
    prof = step_time_profile(8, AsyncConfig(staleness=3, skew=4))
    np.testing.assert_array_equal(
        prof, step_time_profile(8, AsyncConfig(staleness=3, skew=4)))
    assert prof.min() == 1 and prof.max() == 4


# ---------------------------------------------------------------------------
# A7: host-side work accounting matches the device fire counts
# ---------------------------------------------------------------------------


def test_a7_work_completed_matches_fired_counts():
    cfg = MAvgConfig(algorithm="mavg", num_learners=4, k_steps=2,
                     momentum=0.5,
                     topology=TopologyConfig(kind="async", server=AsyncConfig(
                         staleness=3, step_time=(1, 1, 2, 4))))
    topo = make_topology(cfg)
    state = init_state(PARAMS, cfg, topology=topo)
    step = jax.jit(make_meta_step(mlp_loss, cfg, topology=topo))
    fired = 0.0
    for i in range(10):
        state, m = step(state, _batches(i, 4, 2))
        fired += float(m["fired_count"])
        assert topo.work_completed(i) == int(fired)
    # a synchronous topology completes L blocks per tick
    flat = make_topology(MAvgConfig(num_learners=4, k_steps=2))
    assert flat.work_completed(9) == 40


# ---------------------------------------------------------------------------
# A8: the async suite's acceptance experiment (benchmarks/async_bench.py)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def async_rows(run_bench):
    return run_bench("async_bench")


@pytest.mark.parametrize("kind,cell,field,lo,hi", [
    pytest.param("async_accept", None, "loss_vs_sync_at_equal_samples",
                 None, 1.05, id="loss_vs_sync"),
    pytest.param("async_accept", None, "sync_idle_frac", 0.4, None,
                 id="sync_idle"),
    # model ticks, not a wall clock
    pytest.param("async_accept", None, "wall_clock_speedup", 1.5, None,
                 id="tick_speedup"),
    # modeled per-tick wire of the skewed profile (deterministic)
    pytest.param("async_model", "async_skew4", "inter_bytes",
                 None, 37_849_923_584 * 1.02, id="skew4_inter_bytes"),
])
def test_a8_bench_acceptance(async_rows, kind, cell, field, lo, hi):
    (row,) = [r for r in async_rows
              if r["kind"] == kind and r.get("cell") == cell]
    assert lo is None or row[field] >= lo, (field, row[field])
    assert hi is None or row[field] <= hi, (field, row[field])
