"""Smoke run of M-AVG training on a TPU, through the training launcher.

    python3 chip_smoke.py             # one chip
    python3 chip_smoke.py --chips 4   # the learner-sharded path, 4 chips

One chip: xlstm-350m at full width (bf16 learner copies, L=2 run in
sequence, K=2, B=4, S=512 per learner, flat topology, dense M-AVG) takes
a few meta steps through ``repro.launch.train`` (``build`` ->
``Trainer.run``), then the reduced xlstm config takes the same steps on
the chip and, as the reference, with the jnp meta update on the host CPU
from the same initial state and batches; the losses must agree.

``--chips 4``: the reduced config with L=4 sharded one learner per chip
(``--mesh host``) against the same run on one chip, then xlstm-350m at
full width with L=4, one learner per chip. Nothing else.

Every phase prints what it measured; the last line of standard output is
``{"ok": true, "device": {...}}`` and is printed only when every phase
passed. Without a TPU the script exits 1 before running anything. The
persistent compilation cache goes where ``JAX_COMPILATION_CACHE_DIR``
says, else to ``.jax_cache`` in the checkout.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

FULL = ["--arch", "xlstm-350m", "--full", "--compute-dtype", "bfloat16",
        "--k", "2", "--steps", "4", "--batch", "4", "--seq", "512",
        "--topology", "flat", "--algorithm", "mavg", "--comm", "dense"]
REDUCED = ["--arch", "xlstm-350m", "--k", "2", "--steps", "3",
           "--batch", "4", "--seq", "64"]
LOSS_TOL = 5e-2  # per-step loss agreement (as tests/test_system.py)
GiB = 2 ** 30


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


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def train_steps(argv, clock, label, keep_initial=False):
    """Build a Trainer through the launcher and run its meta steps one by
    one, timing each around ``block_until_ready``. ``keep_initial`` keeps
    a host copy of the initial state for a reference run."""
    import jax

    from repro.core.supervisor import RecoveryPlan
    from repro.launch import train

    args = train.parse_args(argv)
    cfg, loss_fn, make_trainer = train.build(args)
    trainer = make_trainer(RecoveryPlan())
    n_params = sum(trainer.state.spec.sizes)
    learners = ("in sequence" if trainer.mcfg.sequential_learners
                else "vmapped")
    print(f"[{label}] model={cfg.name} params={n_params} "
          f"({n_params / 1e6:.1f}M) L={args.learners} K={args.k} "
          f"B={args.batch} S={args.seq} learner_dtype={args.compute_dtype} "
          f"mesh={args.mesh} learners={learners}", flush=True)
    state0 = jax.device_get(trainer.state) if keep_initial else None
    secs, compile_s = [], []
    for i in range(args.steps):
        c0 = clock.seconds
        t0 = time.perf_counter()
        trainer.run(1, log=None)
        jax.block_until_ready(trainer.state)
        secs.append(time.perf_counter() - t0)
        compile_s.append(clock.seconds - c0)
        print(f"[{label}] step {i} loss={trainer.history[-1]['loss']!r} "
              f"seconds={secs[-1]!r} compile_seconds={compile_s[-1]!r}",
              flush=True)
    losses = [h["loss"] for h in trainer.history]
    check(all(math.isfinite(x) for x in losses), f"{label}: loss not finite")
    return dict(loss_fn=loss_fn, trainer=trainer, losses=losses, secs=secs,
                compile_s=compile_s, state0=state0)


def report_step(run, label):
    """Steady step time, the compiled step's kernels and memory."""
    trainer = run["trainer"]
    steady = run["secs"][1:]
    print(f"[{label}] compile_seconds={sum(run['compile_s'])!r} "
          f"first_step_seconds={run['secs'][0]!r} "
          f"steady_step_seconds={min(steady)!r} (min of {len(steady)}; "
          f"all {steady!r})", flush=True)
    compiled = trainer.compiled_step()
    kernel = "tpu_custom_call" in compiled.as_text()
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    print(f"[{label}] compiled step: tpu_custom_call={kernel} "
          f"memory_analysis args={ma.argument_size_in_bytes} "
          f"temps={ma.temp_size_in_bytes} total={total} "
          f"({total / GiB:.2f} GiB)", flush=True)
    check(kernel, f"{label}: no Pallas kernel in the compiled step")


def peak_bytes(label):
    import jax

    for d in jax.local_devices():
        peak = d.memory_stats()["peak_bytes_in_use"]
        print(f"[{label}] device {d.id} peak_bytes_in_use={peak} "
              f"({peak / GiB:.2f} GiB)", flush=True)


def cpu_reference_losses(run):
    """The same meta steps with the jnp meta update on the host CPU, from
    the run's initial state and on its batches."""
    from dataclasses import replace

    import jax

    from repro.core.meta import make_meta_step

    trainer = run["trainer"]
    cpu = jax.devices("cpu")[0]
    # the run's own batches and learning rates, made on the chip as the
    # Trainer made them, then copied to the host
    steps = range(len(run["losses"]))
    inputs = [jax.device_put(jax.device_get(
        (trainer.batch_fn(jax.random.fold_in(trainer.data_rng, i), i),
         trainer.lr_schedule(i))), cpu) for i in steps]
    step = jax.jit(make_meta_step(
        run["loss_fn"], replace(trainer.mcfg, use_pallas=False)))
    losses = []
    with jax.default_device(cpu):
        state = jax.device_put(run["state0"], cpu)
        for b, lr in inputs:
            state, m = step(state, b, lr=lr)
            losses.append(float(m["loss"]))
    return losses


def compare(a, b, what):
    diff = max(abs(x - y) for x, y in zip(a, b))
    print(f"[compare] {what}: max_abs_loss_diff={diff!r} "
          f"(tolerance {LOSS_TOL})", flush=True)
    check(len(a) == len(b) and diff < LOSS_TOL, f"{what}: losses differ")


def release(run):
    run["trainer"].close()
    run.clear()
    gc.collect()


def one_chip(clock):
    run = train_steps(FULL + ["--learners", "2"], clock, "full")
    report_step(run, "full")
    peak_bytes("full")
    release(run)

    small = train_steps(REDUCED + ["--learners", "2"], clock, "reduced",
                        keep_initial=True)
    compare(small["losses"], cpu_reference_losses(small),
            "reduced chip run vs CPU jnp reference")
    release(small)


def four_chips(clock):
    import jax

    check(len(jax.devices()) >= 4, "--chips 4 needs four devices")
    sharded = train_steps(REDUCED + ["--learners", "4", "--mesh", "host"],
                          clock, "reduced-4chips")
    single = train_steps(REDUCED + ["--learners", "4"], clock,
                         "reduced-1chip")
    compare(sharded["losses"], single["losses"],
            "L=4 sharded over 4 chips vs on one chip")
    release(sharded)
    release(single)

    run = train_steps(FULL + ["--learners", "4", "--mesh", "host"], clock,
                      "full-4chips")
    learners = run["trainer"].state.learners
    print(f"[full-4chips] learner stack {learners.shape} {learners.dtype} "
          f"sharding={learners.sharding.spec}", flush=True)
    for s in learners.addressable_shards:
        print(f"[full-4chips] device {s.device.id}: learners[{s.index[0]}] "
              f"shape={s.data.shape}", flush=True)
    check(all(s.data.shape[0] == 1 for s in learners.addressable_shards),
          "full-4chips: not one learner per device")
    report_step(run, "full-4chips")
    peak_bytes("full-4chips")
    release(run)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4])
    opts = ap.parse_args()

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform!r}); "
              f"nothing was run", file=sys.stderr)
        return 1

    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    print(f"compile cache: {cache}", flush=True)
    clock = CompileClock()
    t0 = time.perf_counter()
    (four_chips if opts.chips == 4 else one_chip)(clock)
    print(f"total compile_seconds={clock.seconds!r} over {clock.count} "
          f"compiles; wall_seconds={time.perf_counter() - t0!r}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
