"""Phase tracing: the names the program gives its work (DESIGN.md §11).

  PT1  the compiled xlstm step names its sub-layers: every matrix product
       of the local phase lies under one of ``obs.mlstm``, ``obs.slstm``,
       ``obs.head`` or ``obs.learner_update``, and the forward that each
       block's checkpoint recomputes is marked ``rematted_computation``.
  PT2  the learner update, and the unpack/repack of the learner planes,
       are ``obs.learner_update`` on the masked and unmasked paths alike.
  PT3  every host step of ``Trainer.run`` is a span: ``obs.batch``,
       ``obs.lr`` and ``obs.dispatch`` nest in each step's
       ``obs.meta_step``, the step counter read is ``obs.step_read``, and
       ``obs.run`` holds them all.
  PT4  a Tracer that starts the device profile puts its spans on the
       profile's clock.
"""
import glob
import re
import time

import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import MAvgConfig, ObsConfig, TrainConfig
from repro.core.meta import _local_phase, init_state
from repro.core.trainer import Trainer
from repro.launch import train
from repro.models.simple import mlp_init, mlp_loss
from repro.obs import Tracer

SUBLAYERS = ("obs.mlstm", "obs.slstm", "obs.head", "obs.learner_update")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
L, K, B, D, H, C = 2, 2, 4, 8, 16, 4


def _op_names(hlo_text: str) -> list[str]:
    return _OP_NAME.findall(hlo_text)


def _batches(rng, step=0):
    kx, ky = jax.random.split(rng)
    return {"x": jax.random.normal(kx, (L, K, B, D)),
            "y": jax.random.randint(ky, (L, K, B), 0, C)}


def test_pt1_compiled_xlstm_step_names_its_sublayers():
    from repro.core.supervisor import RecoveryPlan

    # the launcher's test-size step, at a length the chunkwise mLSTM takes
    args = train.parse_args(["--arch", "xlstm-350m", "--learners", "2",
                             "--k", "2", "--steps", "2", "--batch", "2",
                             "--seq", "64"])
    _cfg, _loss, make_trainer = train.build(args)
    trainer = make_trainer(RecoveryPlan())
    trainer.run(1, log=None)
    names = _op_names(trainer.compiled_step().as_text())
    for scope in SUBLAYERS + ("rematted_computation",):
        assert any(scope in n for n in names), scope
    local = [n for n in names if "obs.local_phase" in n]
    dots = [n for n in local if n.endswith("dot_general")]
    assert dots
    assert [n for n in dots if not any(s in n for s in SUBLAYERS)] == []
    # the recompute is the blocks' forward, never the update's
    assert all("obs.learner_update" not in n for n in local
               if "rematted_computation" in n)


@pytest.mark.parametrize("masked", [False, True])
def test_pt2_learner_update_scope(masked):
    cfg = MAvgConfig(num_learners=L, k_steps=K, learner_lr=0.1,
                     local_momentum=0.5)
    state = init_state(mlp_init(jax.random.PRNGKey(0), D, H, C), cfg)
    steps = jnp.array([2, 1]) if masked else None
    run = jax.jit(lambda ls: _local_phase(
        mlp_loss, ls, state.local_momentum, _batches(jax.random.PRNGKey(1)),
        cfg, jnp.float32(0.1), steps=steps, spec=state.spec))
    names = _op_names(run.lower(state.learners).compile().as_text())
    update = [n for n in names if "obs.learner_update" in n]
    # the unpack's slices, the repack's concatenation, the SGD step's
    # subtraction and, masked, the select of the kept steps
    wanted = ["slice", "concatenate", "sub"] + (["select_n"] if masked
                                                else [])
    for op in wanted:
        assert any(n.endswith("/" + op) for n in update), op
    # the model's own forward and backward are not the update's
    assert not any("jvp(" in n for n in update)


def test_pt3_trainer_spans_every_host_step():
    mcfg = MAvgConfig(algorithm="mavg", num_learners=L, k_steps=K,
                      learner_lr=0.1, momentum=0.6)
    cfg = TrainConfig(model=None, mavg=mcfg, batch_per_learner=B,
                      meta_steps=2, log_every=1,
                      obs=ObsConfig(sink="memory", trace=True))
    trainer = Trainer(cfg, mlp_loss,
                      init_params_fn=lambda rng: mlp_init(rng, D, H, C),
                      batch_fn=_batches)
    trainer.run(2, log=None)
    events = trainer.tracer.events

    def within(outer):
        return [{n for n, t0, dur in events
                 if n != outer and s0 <= t0 and t0 + dur <= s0 + d0}
                for name, s0, d0 in events if name == outer]

    (in_run,) = within("obs.run")
    assert {"obs.step_read", "obs.meta_step", "obs.host_flush"} <= in_run
    assert [n for n, _, _ in events].count("obs.step_read") == 1
    in_steps = within("obs.meta_step")
    assert len(in_steps) == 2
    for inside in in_steps:
        assert {"obs.batch", "obs.lr", "obs.dispatch"} <= inside


def test_pt4_spans_on_the_profile_clock(tmp_path):
    from jax.profiler import ProfileData

    jnp.zeros(()).block_until_ready()
    tracer = Tracer(enabled=True)
    time.sleep(0.05)  # the Tracer's own origin lies well before the profile
    with tracer.session(profiler_dir=str(tmp_path)):
        time.sleep(0.1)
        with tracer.span("obs.probe"):
            jnp.ones(4).block_until_ready()
    (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    profiled = [e.start_ns / 1e9
                for plane in ProfileData.from_file(path).planes
                if plane.name == "/host:CPU"
                for line in plane.lines for e in line.events
                if e.name == "obs.probe"]
    (recorded,) = [t0 for n, t0, _ in tracer.events if n == "obs.probe"]
    assert len(profiled) == 1
    assert abs(profiled[0] - recorded) < 5e-3
