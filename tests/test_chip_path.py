"""The pieces of the training path that run on the chip, checked on CPU.

  CP1  ``launch/train.py`` end to end at a tiny size, with and without the
       host mesh, through ``main``; ``parse_args`` + ``build`` are the
       entry points ``chip_smoke.py`` drives.
  CP2  the compile cache goes where ``JAX_COMPILATION_CACHE_DIR`` says,
       else to the fixed ``.jax_cache`` in the checkout.
  CP3  ``chip_smoke.py`` refuses to run without a TPU and prints no result.
  CP4  ``use_pallas`` left unset resolves from the platform (off on CPU);
       an explicit value is kept.
  CP5  the local phase without learner momentum carries no momentum
       stack, bitwise the same as carrying the old all-zero one.
  CP6  the sparse bigram teacher (full-width vocabularies) samples only
       its successors; small vocabularies keep the dense table.
  CP7  roofline peaks are a table keyed by device kind; an unknown kind
       raises.
  CP8  a device profile that was asked for and cannot start raises.
  CP9  learners run in sequence (the launcher's choice without a mesh)
       give the vmapped local phase's planes bitwise.
"""
import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import MAvgConfig
from repro.core.meta import _local_phase, init_state
from repro.launch import compile_cache, train
from repro.models.simple import mlp_init, mlp_loss

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = ["--arch", "xlstm-350m", "--learners", "2", "--k", "2", "--steps",
        "2", "--batch", "2", "--seq", "16"]


@pytest.mark.parametrize("mesh", ["none", "host"])
def test_cp1_train_main_tiny(mesh, monkeypatch, tmp_path, capsys):
    # an explicit cache dir: JAX read the (unset) variable at import, so
    # nothing is cached from this process and main sets no other dir
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    train.main(TINY + ["--mesh", mesh, "--compute-dtype", "bfloat16"])
    out = capsys.readouterr().out
    assert "final train loss" in out and "eval loss" in out


@pytest.mark.parametrize("mesh", ["none", "host"])
def test_cp1_build_compiles_the_step_once(mesh):
    """After the first step nothing compiles again: not the later steps,
    not ``compiled_step`` (which hands back the executable that ran)."""
    from repro.core.supervisor import RecoveryPlan

    compiles = []

    def listen(name, _secs, **_kw):
        if name == "/jax/core/compile/backend_compile_duration":
            compiles.append(name)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        args = train.parse_args(TINY + ["--mesh", mesh])
        _cfg, _loss, make_trainer = train.build(args)
        trainer = make_trainer(RecoveryPlan())
        trainer.run(1, log=None)
        n_first = len(compiles)
        trainer.run(2, log=None)
        trainer.compiled_step()
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert len(compiles) == n_first
    assert np.isfinite(trainer.history[-1]["loss"])
    if mesh == "host":
        assert trainer.state.learners.sharding.spec[0] == "data"


def test_cp2_cache_dir_from_env(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_cp2_cache_dir_default_in_checkout(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    try:
        got = compile_cache.enable_compile_cache()
        assert got == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_cp3_chip_smoke_refuses_cpu(monkeypatch, capsys):
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    monkeypatch.setattr(sys, "argv", ["chip_smoke.py"])
    assert smoke.main() == 1
    assert '"ok"' not in capsys.readouterr().out


def test_cp4_use_pallas_resolves_from_platform():
    from repro.comm import make_reducer_for
    from repro.configs.base import CommConfig
    from repro.kernels.ops import resolve_use_pallas
    from repro.topology import make_topology

    assert MAvgConfig().use_pallas is None
    assert CommConfig().use_pallas is None
    assert resolve_use_pallas(None) is False  # the tests run on CPU
    assert resolve_use_pallas(True) is True
    assert resolve_use_pallas(False) is False
    assert make_topology(MAvgConfig()).cfg.use_pallas is False
    assert make_topology(MAvgConfig(use_pallas=True)).cfg.use_pallas is True
    r = make_reducer_for(CommConfig(scheme="int8", error_feedback=False))
    assert r.use_pallas is False


def test_cp5_no_momentum_stack_is_bitwise_neutral():
    L, K, B, D, H, C = 3, 2, 4, 8, 16, 4
    cfg = MAvgConfig(num_learners=L, k_steps=K, learner_lr=0.1)
    state = init_state(mlp_init(jax.random.PRNGKey(0), D, H, C), cfg)
    kx, ky = jax.random.split(jax.random.PRNGKey(1))
    batches = {"x": jax.random.normal(kx, (L, K, B, D)),
               "y": jax.random.randint(ky, (L, K, B), 0, C)}
    zeros = jnp.zeros_like(state.learners)

    @jax.jit
    def run(learners, mom):
        return _local_phase(mlp_loss, learners, mom, batches, cfg,
                            jnp.float32(0.1), spec=state.spec)[:5]

    new = run(state.learners, None)
    old = run(state.learners, zeros)
    assert new[1] is None and old[1] is not None
    np.testing.assert_array_equal(np.asarray(old[1]), np.asarray(zeros))
    for a, b in zip((new[0],) + new[2:], (old[0],) + old[2:]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("masked", [False, True])
@pytest.mark.parametrize("local_momentum", [0.0, 0.5])
def test_cp9_sequential_learners_match_vmap(masked, local_momentum):
    L, K, B, D, H, C = 3, 2, 4, 8, 16, 4
    kx, ky = jax.random.split(jax.random.PRNGKey(1))
    batches = {"x": jax.random.normal(kx, (L, K, B, D)),
               "y": jax.random.randint(ky, (L, K, B), 0, C)}
    steps = jnp.array([2, 1, 0]) if masked else None
    out = []
    for sequential in (False, True):
        cfg = MAvgConfig(num_learners=L, k_steps=K, learner_lr=0.1,
                         local_momentum=local_momentum,
                         sequential_learners=sequential)
        state = init_state(mlp_init(jax.random.PRNGKey(0), D, H, C), cfg)
        run = jax.jit(lambda ls, s=state, c=cfg: _local_phase(
            mlp_loss, ls, s.local_momentum, batches, c, jnp.float32(0.1),
            steps=steps, spec=s.spec))
        out.append(jax.tree.leaves(run(state.learners)))
    assert len(out[0]) == len(out[1])
    for a, b in zip(*out):
        a, b = np.asarray(a), np.asarray(b)
        if a.ndim >= 2:  # the learner and momentum planes: bitwise
            np.testing.assert_array_equal(a, b)
        else:  # the step sums (over learners, in another order)
            np.testing.assert_allclose(a, b, rtol=1e-6, atol=0)


def test_cp9_launcher_runs_learners_in_sequence_without_mesh():
    for mesh, sequential in (("none", True), ("host", False)):
        args = train.parse_args(TINY + ["--mesh", mesh])
        _cfg, _loss, make_trainer = train.build(args)
        from repro.core.supervisor import RecoveryPlan

        trainer = make_trainer(RecoveryPlan())
        assert trainer.mcfg.sequential_learners is sequential


def test_cp6_sparse_teacher_samples_its_successors():
    from repro.data import (
        bigram_table,
        lm_teacher,
        sample_lm,
        sparse_bigram_teacher,
    )
    from repro.data.synthetic import DENSE_TEACHER_MAX_VOCAB

    small = lm_teacher(1, 64)
    np.testing.assert_array_equal(np.asarray(small),
                                  np.asarray(bigram_table(1, 64)))
    vocab = DENSE_TEACHER_MAX_VOCAB + 1
    succ, logits = lm_teacher(1, vocab)
    ref_succ, _ = sparse_bigram_teacher(1, vocab)
    np.testing.assert_array_equal(np.asarray(succ), np.asarray(ref_succ))
    toks = np.asarray(sample_lm(jax.random.PRNGKey(2), (succ, logits), 4, 32))
    assert toks.shape == (4, 32) and toks.min() >= 0 and toks.max() < vocab
    succ = np.asarray(succ)
    for row in toks:
        for a, b in zip(row[:-1], row[1:]):
            assert b in succ[a]


def test_cp7_roofline_peaks_by_device_kind():
    from repro.configs import get_config
    from repro.configs.base import INPUT_SHAPES
    from repro.roofline import DEVICE_PEAKS, compute_terms, device_peaks

    for kind, row in DEVICE_PEAKS.items():
        assert row["source"] and device_peaks(kind) is row
    with pytest.raises(ValueError, match="no roofline peaks"):
        device_peaks("cpu")
    shape = next(iter(INPUT_SHAPES.values()))
    kw = dict(arch="a", shape=shape, mesh_name="m", chips=1, hlo_flops=1.0,
              hlo_bytes=1.0, collective_bytes=0.0,
              cfg=get_config("xlstm-350m"))
    assert compute_terms(**kw).compute_s > 0
    with pytest.raises(ValueError):
        compute_terms(**kw, device_kind="cpu")


def test_cp8_profiler_start_failure_raises(monkeypatch, tmp_path):
    from repro.obs import Tracer

    def refuse(_path):
        raise RuntimeError("no profiler here")

    monkeypatch.setattr(jax.profiler, "start_trace", refuse)
    tracer = Tracer(enabled=True)
    with pytest.raises(RuntimeError, match="no profiler"):
        with tracer.session(profiler_dir=str(tmp_path)):
            pass
