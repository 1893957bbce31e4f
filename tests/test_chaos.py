"""repro.chaos acceptance tests (DESIGN.md §13).

The contract has three legs, each pinned here:

1. **Determinism / replay** — a ``FaultSchedule`` is a pure function of
   (ChaosConfig, num_learners, salt); retries (salt > 0) drop transient
   faults but keep sticky ones, and the config STRUCTURE (membership
   schedule, straggle profile) survives the salt so checkpoints restore
   across attempts.
2. **Off == bitwise identity** — every injector disabled (idle
   corruptor installed, finite guard on) reproduces the vanilla run
   bit-for-bit, so chaos can ride in the default config path.
3. **Supervised recovery** — an injected fault halts the run, the
   Supervisor rolls back through the verified chain and completes the
   target steps with schema-valid fault/recovery telemetry; a sticky
   fault exhausts the bounded retry budget instead of looping forever.

CH6 holds benchmarks/chaos_bench.py's acceptance experiment at smoke size:
under the standard fault schedule the supervised run lands within 5% of
the fault-free loss with a finite state, a torn save falls back bit-exactly,
and recovery stays within 3 retries.
"""
import dataclasses
import importlib.util
import os
from types import SimpleNamespace

import pytest

import jax
import numpy as np

from repro.chaos import (
    ChaosConfig,
    FaultSchedule,
    FaultSpec,
    PayloadCorruptor,
    apply_chaos,
    standard_chaos,
    wrap_batch_fn,
)
from repro.checkpoint import save_state
from repro.configs.base import (
    AsyncConfig,
    MAvgConfig,
    ObsConfig,
    TopologyConfig,
    TrainConfig,
)
from repro.core import (
    RecoveryExhausted,
    RecoveryPolicy,
    Supervisor,
    Trainer,
)
from repro.core.meta import init_state, make_meta_step
from repro.data import classif_batch_fn
from repro.models.simple import mlp_init, mlp_loss
from repro.obs import HealthHalt
from repro.utils.retry import retry_io

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

L, K, B, D, C = 2, 2, 4, 8, 4


def _mcfg(**kw):
    kw.setdefault("num_learners", L)
    kw.setdefault("learner_lr", 0.1)
    return MAvgConfig(algorithm="mavg", k_steps=K, momentum=0.6, **kw)


def _batches(seed):
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


# ---------------------------------------------------------------------------
# CH1: schedule determinism + salt semantics
# ---------------------------------------------------------------------------


def test_ch1_schedule_deterministic():
    cfg = standard_chaos(4, 32, seed=7)
    a, b = FaultSchedule(cfg, 4), FaultSchedule(cfg, 4)
    for name in ("nan", "inf", "scale", "xor", "pos", "crash"):
        np.testing.assert_array_equal(getattr(a, name), getattr(b, name))
    np.testing.assert_array_equal(a.straggle_extra, b.straggle_extra)
    assert a.save_faults == b.save_faults


def test_ch1_salt_drops_transient_keeps_sticky():
    cfg = ChaosConfig(seed=3, horizon=8, faults=(
        FaultSpec("nan_batch", step=1, learner=0),               # transient
        FaultSpec("payload_scale", step=2, learner=1,
                  magnitude=2.0, sticky=True),                   # broken hw
    ))
    s0 = FaultSchedule(cfg, L)
    s1 = FaultSchedule(cfg, L, salt=1)
    assert s0.nan[1, 0] == 1.0 and s0.scale[2, 1] == 2.0
    # the retry replays the transient fault clean...
    assert not s1.nan.any()
    # ...but the sticky one re-fires identically
    np.testing.assert_array_equal(s1.scale, s0.scale)


def test_ch1_out_of_horizon_steps_are_clean():
    cfg = ChaosConfig(seed=0, horizon=4,
                      faults=(FaultSpec("nan_batch", step=3, learner=0),))
    sched = FaultSchedule(cfg, L)
    nan, inf = sched.batch_fault_at(3)
    assert nan.any()
    for step in (-1, 4, 100):
        nan, inf = sched.batch_fault_at(step)
        assert not (nan.any() or inf.any())
    assert sched.save_fault(100) is None


def test_ch1_config_validation():
    with pytest.raises(AssertionError):  # fault beyond the horizon
        ChaosConfig(horizon=4, faults=(FaultSpec("crash", step=3,
                                                 duration=2),))
    with pytest.raises(AssertionError):  # unknown kind
        FaultSpec("meteor_strike", step=0)
    with pytest.raises(AssertionError):  # save faults target the run
        FaultSpec("torn_save", step=0, learner=1)


# ---------------------------------------------------------------------------
# CH2: every injector off => bitwise identical to vanilla
# ---------------------------------------------------------------------------


def test_ch2_injectors_off_bitwise_identical():
    """Idle corruptor installed + finite guard on == no chaos at all, at
    the bit level — the pin that lets chaos live in the default path."""
    empty = FaultSchedule(ChaosConfig(seed=0, horizon=8, faults=()), L)
    assert not (empty.any_batch_faults or empty.any_payload_faults
                or empty.any_crash_faults)
    assert wrap_batch_fn(lambda rng, s: _batches(0), empty)(None, 0) \
        is not None  # no-fault schedule returns batch_fn itself
    plain = jax.jit(make_meta_step(mlp_loss, _mcfg()))
    armed = jax.jit(make_meta_step(mlp_loss, _mcfg(finite_guard=True),
                                   chaos=PayloadCorruptor(empty)))
    sp = sa = init_state(mlp_init(jax.random.PRNGKey(0), D, 16, C),
                         _mcfg())
    for i in range(3):
        sp, _ = plain(sp, _batches(i))
        sa, ma = armed(sa, _batches(i))
    assert _leaves_equal(sp, sa)
    assert float(ma["nonfinite_learners"]) == 0.0


def test_ch2_apply_chaos_no_structural_faults_is_identity():
    mcfg = _mcfg()
    chaos = ChaosConfig(seed=0, horizon=8,
                        faults=(FaultSpec("nan_batch", step=1, learner=0),))
    assert apply_chaos(mcfg, chaos) is mcfg  # the identical object


# ---------------------------------------------------------------------------
# CH3: per-layer injection
# ---------------------------------------------------------------------------


def test_ch3_nan_batch_guard_keeps_state_finite():
    """A poisoned batch NaNs the target learner's local phase; the
    in-step finite guard resets it to the broadcast global params
    (skip-and-decay), reports it in ``nonfinite_learners``, and no
    non-finite value ever reaches MetaState."""
    chaos = ChaosConfig(seed=0, horizon=4,
                        faults=(FaultSpec("nan_batch", step=0, learner=0),))
    sched = FaultSchedule(chaos, L)
    poisoned = wrap_batch_fn(lambda rng, s: _batches(0), sched)(None, 0)
    assert np.isnan(np.asarray(poisoned["x"])[0]).all()
    assert np.isfinite(np.asarray(poisoned["x"])[1]).all()

    step = jax.jit(make_meta_step(mlp_loss, _mcfg(finite_guard=True)))
    state = init_state(mlp_init(jax.random.PRNGKey(0), D, 16, C), _mcfg())
    state, metrics = step(state, poisoned)
    assert float(metrics["nonfinite_learners"]) == 1.0
    for x in jax.tree.leaves((state.global_params, state.momentum,
                              state.learners)):
        assert np.isfinite(np.asarray(x)).all()


def test_ch3_payload_corruption_deterministic_and_localized():
    """Payload corruption fires exactly on its scheduled step, changes
    the trajectory, and replays identically."""
    chaos = ChaosConfig(seed=0, horizon=8, faults=(
        FaultSpec("payload_scale", step=1, learner=1, magnitude=3.0),
    ))
    cor = PayloadCorruptor(FaultSchedule(chaos, L))
    assert cor.active
    plain = jax.jit(make_meta_step(mlp_loss, _mcfg()))
    dirty = jax.jit(make_meta_step(mlp_loss, _mcfg(), chaos=cor))

    def run(step_fn):
        s = init_state(mlp_init(jax.random.PRNGKey(0), D, 16, C), _mcfg())
        out = []
        for i in range(3):
            s, _ = step_fn(s, _batches(i))
            out.append(s)
        return out

    sp, sd, sd2 = run(plain), run(dirty), run(dirty)
    assert _leaves_equal(sp[0], sd[0])        # step 0: quiet => bitwise
    assert not _leaves_equal(sp[1], sd[1])    # step 1: fault fired
    for a, b in zip(sd, sd2):                 # replay identical
        assert _leaves_equal(a, b)


def test_ch3_bitflip_is_a_real_bit():
    """payload_bitflip changes exactly ONE element of one leaf, by an
    XOR of the configured bit — a bit-level event, not a rescale."""
    chaos = ChaosConfig(seed=1, horizon=4, faults=(
        FaultSpec("payload_bitflip", step=0, learner=1, bit=23),
    ))
    cor = PayloadCorruptor(FaultSchedule(chaos, L))
    learners = {
        "w": jax.numpy.ones((L, 3, 5), jax.numpy.float32),
        "b": jax.numpy.zeros((L, 7), jax.numpy.float32),
    }
    out = cor(learners, jax.numpy.int32(0))
    diffs = [
        int((np.asarray(out[k]) != np.asarray(learners[k])).sum())
        for k in ("w", "b")
    ]
    assert sum(diffs) == 1  # exactly one element anywhere
    a = np.asarray(learners["w"]).view(np.int32)
    bflip = np.asarray(out["w"]).view(np.int32)
    changed = a != bflip
    if changed.any():
        assert (a[changed] ^ bflip[changed] == (1 << 23)).all()
    # learner 0 untouched bitwise
    assert np.array_equal(np.asarray(out["w"])[0],
                          np.asarray(learners["w"])[0])


def test_ch3_crash_maps_to_membership_schedule():
    """Crash windows become rows of an explicit elastic membership
    schedule; a retry (salt > 0) keeps the STRUCTURE (same-shape
    schedule, checkpoint-compatible) but drops the injected absences."""
    mcfg = _mcfg(num_learners=4, topology=TopologyConfig(
        kind="async", server=AsyncConfig(staleness=2)))
    chaos = ChaosConfig(seed=0, horizon=6, faults=(
        FaultSpec("crash", step=1, learner=2, duration=2),
    ))
    out = apply_chaos(mcfg, chaos)
    rows = np.asarray(out.topology.elastic.schedule, np.float32)
    assert rows.shape == (6, 4)
    assert rows[1, 2] == 0.0 and rows[2, 2] == 0.0  # the crash window
    assert rows[0, 2] == 1.0 and rows[3, 2] == 1.0  # present outside it
    assert (rows.sum(axis=1) >= 1.0).all()

    retry = apply_chaos(mcfg, chaos, salt=1)
    rows1 = np.asarray(retry.topology.elastic.schedule, np.float32)
    assert rows1.shape == rows.shape  # structure survives the salt
    assert (rows1 == 1.0).all()       # the transient absences do not

    with pytest.raises(ValueError, match="flat"):
        apply_chaos(_mcfg(num_learners=4), chaos)


def test_ch3_straggle_lands_on_async_profile():
    mcfg = _mcfg(num_learners=2, topology=TopologyConfig(
        kind="async", server=AsyncConfig(staleness=1)))
    chaos = ChaosConfig(seed=0, horizon=8, faults=(
        FaultSpec("straggle", step=0, learner=1, magnitude=3.0),
    ))
    out = apply_chaos(mcfg, chaos)
    prof = out.topology.server.step_time
    assert prof[1] - prof[0] == 3
    assert out.topology.server.staleness >= max(prof) - 1


# ---------------------------------------------------------------------------
# CH4: supervised recovery
# ---------------------------------------------------------------------------


def _check_telemetry():
    spec = importlib.util.spec_from_file_location(
        "check_telemetry", os.path.join(_ROOT, "tools", "check_telemetry.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _make_trainer_factory(tmp_path, chaos, *, steps=8):
    ckpt = str(tmp_path / "ckpt")
    run_dir = str(tmp_path / "run")

    def make_trainer(plan):
        mcfg = _mcfg(learner_lr=0.1 * plan.lr_scale, finite_guard=True)
        tcfg = TrainConfig(
            model=None, mavg=mcfg, batch_per_learner=B, meta_steps=steps,
            seed=0, log_every=1, checkpoint_dir=ckpt, checkpoint_every=2,
            chaos=chaos, data_salt=plan.data_salt,
            obs=ObsConfig(sink="jsonl", run_dir=run_dir, health=True),
        )
        return Trainer(
            tcfg, mlp_loss,
            init_params_fn=lambda rng: mlp_init(rng, D, 16, C),
            batch_fn=classif_batch_fn(D, C, L, K, B),
        )

    return make_trainer, ckpt, run_dir


def test_ch4_supervised_recovery_completes(tmp_path):
    """A transient NaN burst halts the run; the supervisor rolls back
    through the verified chain and the retry (fault dropped by the salt)
    completes the target steps with schema-valid telemetry."""
    steps = 8
    chaos = ChaosConfig(seed=0, horizon=steps, faults=(
        FaultSpec("nan_batch", step=3, learner=0),
    ))
    make_trainer, ckpt, run_dir = _make_trainer_factory(
        tmp_path, chaos, steps=steps)
    sup = Supervisor(make_trainer, target_steps=steps, checkpoint_dir=ckpt)
    trainer, history = sup.run(log=None)
    assert int(trainer.state.step) == steps
    for x in jax.tree.leaves((trainer.state.global_params,
                              trainer.state.learners)):
        assert np.isfinite(np.asarray(x)).all()

    faults = [r for r in sup.records if r.get("kind") == "fault"]
    recoveries = [r for r in sup.records if r.get("kind") == "recovery"]
    assert faults and faults[0]["fault"] == "nonfinite_loss"
    assert faults[0]["learner"] == 0  # the schedule's attribution oracle
    assert recoveries and recoveries[0]["attempt"] == 1
    assert "rollback" in recoveries[0]["policy"]
    trainer.close()

    ct = _check_telemetry()
    schema = ct.load_schema(os.path.join(_ROOT, "tools",
                                         "telemetry_schema.json"))
    with open(os.path.join(run_dir, "run.jsonl")) as f:
        assert ct.check_stream(f, schema) == []


def test_ch4_sticky_fault_exhausts_retries(tmp_path):
    """A sticky fault re-fires on every salt: the bounded budget runs
    out, RecoveryExhausted carries the fault, and the
    recovery_exhausted watchdog alert lands in the record stream."""
    steps = 8
    chaos = ChaosConfig(seed=0, horizon=steps, faults=(
        FaultSpec("nan_batch", step=1, learner=0, sticky=True),
    ))
    make_trainer, ckpt, _ = _make_trainer_factory(
        tmp_path, chaos, steps=steps)
    sup = Supervisor(make_trainer, target_steps=steps, checkpoint_dir=ckpt,
                     policy=RecoveryPolicy(max_retries=1))
    with pytest.raises(RecoveryExhausted) as ei:
        sup.run(log=None)
    assert ei.value.fault["fault"] == "nonfinite_loss"
    assert any(r.get("rule") == "recovery_exhausted" for r in sup.records)


def test_ch4_rollback_is_causal_and_walks_back(tmp_path):
    """The supervisor never resumes from a snapshot at/after the fault
    step (the emergency halt snapshot verifies finite yet carries the
    sick state), and a retry that stalls without progress distrusts the
    snapshot it resumed from — one snapshot further back per stalled
    attempt, down to a scratch restart."""
    tree = {"a": np.arange(4.0)}
    ckpt = str(tmp_path)
    for s in (2, 4, 5):  # 5 plays the emergency halt snapshot
        save_state(ckpt, tree, s)

    class _FakeTrainer:
        def __init__(self):
            self.state = SimpleNamespace(step=0)
            self.history = []
            self._monitor = None

        def restore(self, path):
            from repro.checkpoint import checkpoint_step
            self.state.step = checkpoint_step(path)

        def run(self, remaining, log=None):
            self.state.step = 5
            raise HealthHalt({"rule": "loss_divergence", "metric": "loss",
                              "value": 99.0, "meta_step": 4})

        def emit(self, record):
            pass

        def close(self):
            pass

    sup = Supervisor(lambda plan: _FakeTrainer(), target_steps=10,
                     checkpoint_dir=ckpt,
                     policy=RecoveryPolicy(max_retries=3))
    with pytest.raises(RecoveryExhausted):
        sup.run(log=None)
    resumes = [
        (r["meta_step"], r["resume_path"])
        for r in sup.records if r.get("kind") == "recovery"
    ]
    # never step 5; then 4 -> 2 -> scratch as the stall deepens
    assert [s for s, _ in resumes] == [4, 2, 0]
    assert resumes[-1][1] is None


def test_ch4_quarantine_masks_then_readmits(tmp_path):
    """Quarantine rewrites the membership window after the resume step
    (probation), leaves later rows untouched (readmission), and never
    empties a row."""
    mcfg = _mcfg(num_learners=4, topology=TopologyConfig(
        kind="async", server=AsyncConfig(staleness=2)))
    chaos = ChaosConfig(seed=0, horizon=8, faults=(
        FaultSpec("crash", step=6, learner=3),
    ))
    tcfg = TrainConfig(
        model=None, mavg=mcfg, batch_per_learner=B, meta_steps=8, seed=0,
        chaos=chaos, obs=ObsConfig(sink="none"),
    )
    trainer = Trainer(
        tcfg, mlp_loss,
        init_params_fn=lambda rng: mlp_init(rng, D, 16, C),
        batch_fn=classif_batch_fn(D, C, 4, K, B),
    )
    sup = Supervisor(lambda plan: trainer, target_steps=8,
                     checkpoint_dir=None,
                     policy=RecoveryPolicy(quarantine_steps=2))
    sup._quarantine(trainer, (1,), 2)
    m = np.asarray(trainer.state.topo["membership"])
    assert m[2, 1] == 0.0 and m[3, 1] == 0.0   # probation window
    assert m[4, 1] == 1.0 and m[1, 1] == 1.0   # readmitted / untouched
    assert (m.sum(axis=1) >= 1.0).all()
    trainer.close()


# ---------------------------------------------------------------------------
# CH5: shared retry helper + sink resilience
# ---------------------------------------------------------------------------


def test_ch5_retry_io_backoff_then_success():
    calls = {"n": 0}
    delays = []

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise OSError("transient")
        return "ok"

    assert retry_io(flaky, sleep=delays.append) == "ok"
    assert calls["n"] == 3
    assert delays == [0.05, 0.05 * 2.0]  # exponential backoff observed


def test_ch5_retry_io_exhausts_loudly():
    calls = {"n": 0}

    def dead():
        calls["n"] += 1
        raise OSError("gone")

    with pytest.raises(OSError, match="gone"):
        retry_io(dead, attempts=3, sleep=lambda d: None)
    assert calls["n"] == 3


def test_ch5_retry_io_only_retries_transient_classes():
    calls = {"n": 0}

    def broken():
        calls["n"] += 1
        raise ValueError("a bug, not an I/O hiccup")

    with pytest.raises(ValueError):
        retry_io(broken, sleep=lambda d: None)
    assert calls["n"] == 1


def test_ch5_jsonl_sink_survives_transient_oserror(tmp_path):
    from repro.obs.sink import JsonlSink

    path = str(tmp_path / "run.jsonl")
    sink = JsonlSink(path)

    real = sink._f
    flaky = SimpleNamespace(
        fails=1,
        write=lambda s: _flaky_write(flaky, real, s),
        flush=real.flush,
        close=real.close,
        closed=False,
    )
    sink._f = flaky
    sink.append({"kind": "step", "meta_step": 0, "loss": 1.0})
    sink.flush()
    real.close()
    with open(path) as f:
        lines = [ln for ln in f if ln.strip()]
    assert len(lines) == 1 and '"loss": 1.0' in lines[0]


def _flaky_write(self, real, s):
    if self.fails:
        self.fails -= 1
        raise OSError("EAGAIN")
    return real.write(s)


def test_ch5_backoff_schedule_seeded_jitter():
    """The jittered backoff schedule is a pure function of (seed, jitter):
    replayable bit-for-bit, bounded by [base*f^i, base*f^i*(1+jitter)],
    and jitter=0 (the default) IS the plain exponential schedule."""
    from repro.utils import backoff_schedule

    assert backoff_schedule(4) == [0.05, 0.05 * 2.0, 0.05 * 4.0]
    a = backoff_schedule(5, jitter=0.5, seed=11)
    assert a == backoff_schedule(5, jitter=0.5, seed=11)  # deterministic
    assert a != backoff_schedule(5, jitter=0.5, seed=12)  # seed-keyed
    assert a != backoff_schedule(5, jitter=0.0, seed=11)  # jitter is real
    for i, d in enumerate(a):
        base = 0.05 * 2.0 ** i
        assert base <= d <= base * 1.5

    calls = {"n": 0}
    delays = []

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise OSError("transient")
        return "ok"

    # retry_io sleeps exactly the schedule's delays, in order
    assert retry_io(flaky, jitter=0.5, seed=11, sleep=delays.append) == "ok"
    assert delays == a[:2]


def test_ch4_quarantine_hysteresis_extends_probation(tmp_path):
    """``readmit_clean_windows=M`` stretches the probation window to
    M x quarantine_steps — a flapping learner must stay clean for M
    windows before readmission; M=1 is the old single-window behavior
    (pinned by test_ch4_quarantine_masks_then_readmits)."""
    mcfg = _mcfg(num_learners=4, topology=TopologyConfig(
        kind="async", server=AsyncConfig(staleness=2)))
    chaos = ChaosConfig(seed=0, horizon=8, faults=(
        FaultSpec("crash", step=6, learner=3),
    ))
    tcfg = TrainConfig(
        model=None, mavg=mcfg, batch_per_learner=B, meta_steps=8, seed=0,
        chaos=chaos, obs=ObsConfig(sink="none"),
    )
    trainer = Trainer(
        tcfg, mlp_loss,
        init_params_fn=lambda rng: mlp_init(rng, D, 16, C),
        batch_fn=classif_batch_fn(D, C, 4, K, B),
    )
    sup = Supervisor(lambda plan: trainer, target_steps=8,
                     checkpoint_dir=None,
                     policy=RecoveryPolicy(quarantine_steps=2,
                                           readmit_clean_windows=2))
    sup._quarantine(trainer, (1,), 2)
    m = np.asarray(trainer.state.topo["membership"])
    assert (m[2:6, 1] == 0.0).all()            # 2 x 2 probation rows
    assert m[6, 1] == 1.0 and m[1, 1] == 1.0   # readmitted / untouched
    assert (m.sum(axis=1) >= 1.0).all()
    trainer.close()


def test_ch4_readmit_clean_windows_validation():
    with pytest.raises(AssertionError):
        RecoveryPolicy(readmit_clean_windows=0)


# ---------------------------------------------------------------------------
# CH6: the chaos suite's acceptance experiment (benchmarks/chaos_bench.py)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def chaos_accept(run_bench):
    (row,) = [r for r in run_bench("chaos_bench")
              if r["kind"] == "chaos_accept"]
    return row


@pytest.mark.parametrize("field,lo,hi", [
    # supervised run within 5% of the fault-free loss at equal samples
    ("loss_vs_fault_free", None, 1.05),
    # final state and every retained snapshot finite
    ("state_finite", True, None),
    # a torn save falls back to the last verified snapshot bit-exactly
    ("resume_verified", True, None),
    # recovery within the bounded retry budget
    ("retries_used", None, 3),
], ids=["loss_vs_fault_free", "state_finite", "resume_verified",
        "retries_used"])
def test_ch6_bench_acceptance(chaos_accept, field, lo, hi):
    value = chaos_accept[field]
    assert lo is None or value >= lo, (field, value)
    assert hi is None or value <= hi, (field, value)
