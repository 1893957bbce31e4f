"""The system under test, driven as its users drive it: the training
launcher's ``parse_args`` -> ``build`` -> ``make_trainer``, then
``Trainer.run``.

A cell's configuration file (``bench/configs``) and traffic file
(``bench/traffic``) become launcher arguments here; nothing else of the
program is configured. The one thing the launcher has no option for is
the seed of the weights (``TrainConfig.seed``): it is passed by giving the
launcher's ``TrainConfig`` a default for it while the trainer is made.
The data seed goes through ``RecoveryPlan.data_salt``.
"""
from __future__ import annotations

import time
from functools import partial

import ml_dtypes
import numpy as np

# launcher option -> key of the configuration or traffic file
CONFIG_ARGS = {"--arch": "arch", "--compute-dtype": "learner_dtype",
               "--learners": "learners", "--mesh": "mesh",
               "--topology": "topology", "--algorithm": "algorithm",
               "--comm": "comm", "--momentum": "momentum", "--lr": "lr"}
TRAFFIC_ARGS = {"--k": "k", "--batch": "batch", "--seq": "seq",
                "--steps": "schedule_steps"}


def seeds(seed: int) -> tuple[int, int]:
    """(weight seed, data salt) of a run's ``--seed``; the salt is never
    0, which the trainer reads as "no salt"."""
    return seed % 2 ** 31, seed % (2 ** 31 - 1) + 1


def launcher_argv(config: dict, traffic: dict, trace: bool = False):
    argv = ["--full"] if config["full_width"] else []
    for opt, key in CONFIG_ARGS.items():
        argv += [opt, str(config[key])]
    for opt, key in TRAFFIC_ARGS.items():
        argv += [opt, str(traffic[key])]
    return argv + (["--trace"] if trace else [])


def build_trainer(config: dict, traffic: dict, seed: int,
                  trace: bool = False):
    """(trainer, model config) of one run."""
    from repro.configs import base
    from repro.core.supervisor import RecoveryPlan
    from repro.launch import train

    args = train.parse_args(launcher_argv(config, traffic, trace))
    cfg, _loss_fn, make_trainer = train.build(args)
    for key, value in config["model"].items():
        if getattr(cfg, key) != value:
            raise SystemExit(f"the launcher's {cfg.name} has {key}="
                             f"{getattr(cfg, key)!r}, the configuration "
                             f"file {value!r}")
    weight_seed, salt = seeds(seed)
    train.TrainConfig = partial(base.TrainConfig, seed=weight_seed)
    try:
        trainer = make_trainer(RecoveryPlan(data_salt=salt))
    finally:
        train.TrainConfig = base.TrainConfig
    return trainer, cfg


def _leaf_norms(spec, flat: np.ndarray, base: np.ndarray | None = None
                ) -> dict[str, float]:
    """Per leaf, the norm of ``flat``, or with ``base`` given, of
    ``flat + base - bfloat16(base)``."""
    out = {}
    for path, off, size in zip(spec.paths, spec.offsets, spec.sizes):
        x = flat[off:off + size].astype(np.float64)
        if base is not None:
            b = base[off:off + size]
            x += b.astype(np.float64) - b.astype(ml_dtypes.bfloat16).astype(
                np.float64)
        out[path] = float(np.linalg.norm(x))
    return out


def first_steps(trainer, steps: int, clock) -> dict:
    """Drive the trainer's first ``steps`` meta steps through
    ``Trainer.run``, one call each, and read what the correctness check
    compares: each step's loss; per leaf, the norm of the block momentum
    after the first step, v1 = mean_j w_j - w~0 (``first_grad``), of the
    learners' own movement in it, mean_j w_j - bfloat16(w~0) =
    v1 + w~0 - bfloat16(w~0) (``first_move``), and of the change of the
    meta parameters over all the steps. Also the host seconds and compile
    seconds of each."""
    import jax

    spec = trainer.state.spec
    w0 = np.asarray(jax.device_get(trainer.state.global_params)).reshape(-1)
    out = {"loss": [], "step_s": [], "compile_s": []}
    for n in range(steps):
        c0, t0 = clock.seconds, time.perf_counter()
        trainer.run(1, log=None)
        jax.block_until_ready(trainer.state)
        out["step_s"].append(time.perf_counter() - t0)
        out["compile_s"].append(clock.seconds - c0)
        out["loss"].append(float(trainer.history[-1]["loss"]))
        if n == 0:
            v1 = np.asarray(jax.device_get(trainer.state.momentum))
            out["first_grad"] = _leaf_norms(spec, v1.reshape(-1))
            out["first_move"] = _leaf_norms(spec, v1.reshape(-1), w0)
            del v1
    w = np.asarray(jax.device_get(trainer.state.global_params)).reshape(-1)
    out["change"] = _leaf_norms(spec, w - w0)
    return out


class CompileClock:
    """Sums XLA backend compile seconds as JAX reports them."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.seconds, self.count = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **_kw):
        if name == self.EVENT:
            self.seconds += secs
            self.count += 1
