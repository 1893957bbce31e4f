"""Training launcher: end-to-end M-AVG training of an assigned architecture
(reduced or full-width config) on the devices of one host.

Without ``--full`` it trains the reduced config (the end-to-end example
driver on CPU). ``--full`` keeps the published widths; xlstm-350m at full
width fits one 16 GiB v5e chip with bf16 learner copies
(``--compute-dtype bfloat16``). ``--mesh host`` lays the learners over
every chip of the host, one (n, 1) ('data', 'model') mesh from
``launch/mesh.py``, with the learner stack and the batches sharded on
'data'; without it everything runs on the default device. No pod-scale
mesh is built here — the 256/512-chip programs are compiled, not run, by
``launch/dryrun.py``.

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b \
      --algorithm mavg --learners 4 --k 4 --steps 50
  PYTHONPATH=src python -m repro.launch.train --arch xlstm-350m --full \
      --learners 2 --k 2 --steps 3 --batch 4 --seq 512 \
      --compute-dtype bfloat16
"""
from __future__ import annotations

import argparse

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import (
    ALGORITHMS,
    ASYNC_UPDATES,
    COMM_SCHEMES,
    GOSSIP_GRAPHS,
    OBS_SINKS,
    TOPOLOGIES,
    AsyncConfig,
    CommConfig,
    ROBUST_ESTIMATORS,
    ElasticConfig,
    MAvgConfig,
    ObsConfig,
    RobustConfig,
    TopologyConfig,
    TrainConfig,
    get_config,
)
from repro.core.trainer import Trainer
from repro.data import lm_batch_fn, lm_eval_set
from repro.models import api as model_api
from repro.optim import warmup_cosine
from repro.pack import unpack_params


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b")
    # choices derive from the configs/base.py constants so new algorithms /
    # schemes / topologies show up here without hand-maintained duplication
    ap.add_argument("--algorithm", default="mavg", choices=ALGORITHMS)
    ap.add_argument("--learners", type=int, default=4)
    ap.add_argument("--k", type=int, default=4)
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=0.3)
    ap.add_argument("--momentum", type=float, default=0.7)
    ap.add_argument("--full", action="store_true",
                    help="full-width config (published widths); "
                         "xlstm-350m fits one v5e chip with "
                         "--compute-dtype bfloat16")
    ap.add_argument("--compute-dtype", default="float32",
                    choices=["float32", "bfloat16"],
                    help="dtype of the learner copies (MAvgConfig."
                         "compute_dtype); the meta state stays float32")
    ap.add_argument("--mesh", default="none", choices=["none", "host"],
                    help="host: the learners spread over every chip of "
                         "this host, learner stack and batches sharded "
                         "over 'data' (--learners must divide by the "
                         "chip count)")
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--comm", default="dense", choices=COMM_SCHEMES,
                    help="meta-communication compression scheme (repro.comm)")
    ap.add_argument("--comm-k-frac", type=float, default=0.1,
                    help="kept fraction for the top-k comm schemes")
    ap.add_argument("--no-error-feedback", action="store_true",
                    help="disable the comm error-feedback residual")
    ap.add_argument("--topology", default="flat", choices=TOPOLOGIES,
                    help="meta-level mixing topology (repro.topology)")
    ap.add_argument("--groups", type=int, default=1,
                    help="hierarchical: number of learner groups G")
    ap.add_argument("--outer-every", type=int, default=1,
                    help="hierarchical: cross-group average every H meta steps")
    ap.add_argument("--outer-momentum", type=float, default=0.0,
                    help="hierarchical: block momentum of the outer level")
    ap.add_argument("--gossip-graph", default="ring", choices=GOSSIP_GRAPHS,
                    help="gossip: mixing graph")
    ap.add_argument("--outer-comm", default=None, choices=COMM_SCHEMES,
                    help="cross-group comm scheme (default: same as --comm)")
    ap.add_argument("--group-k", default=None,
                    help="hierarchical: comma-separated per-group local-step "
                         "counts K_g (each <= --k), e.g. --group-k 2,4")
    ap.add_argument("--async-staleness", type=int, default=0,
                    help="async: staleness bound tau (center updates a "
                         "pulled copy may lag behind)")
    ap.add_argument("--async-profile", default=None,
                    help="async: comma-separated per-learner step-time "
                         "profile in meta ticks, e.g. --async-profile "
                         "1,1,2,4 (overrides --async-skew)")
    ap.add_argument("--async-skew", type=int, default=1,
                    help="async: slowest/fastest step-time ratio of the "
                         "seed-generated profile (1 = uniform)")
    ap.add_argument("--async-update", default="mavg", choices=ASYNC_UPDATES,
                    help="async: staleness-decayed update rule")
    ap.add_argument("--async-decay", type=float, default=None,
                    help="async: staleness decay base (default: the block "
                         "momentum, the mu^tau rule)")
    ap.add_argument("--async-seed", type=int, default=0,
                    help="async: seed assigning profile slots to learners")
    ap.add_argument("--elastic-period", type=int, default=0,
                    help="elastic membership schedule length in meta steps "
                         "(0 = everyone always present)")
    ap.add_argument("--elastic-drop", type=float, default=0.25,
                    help="fraction of learners absent per scheduled step")
    ap.add_argument("--elastic-seed", type=int, default=0,
                    help="seed of the deterministic membership schedule")
    ap.add_argument("--obs-sink", default="none", choices=OBS_SINKS,
                    help="structured run log sink (repro.obs): per-step "
                         "telemetry records under a run manifest")
    ap.add_argument("--run-dir", default=None,
                    help="run-log / trace directory (required for the "
                         "jsonl and csv sinks)")
    ap.add_argument("--trace", action="store_true",
                    help="phase span timers + Chrome-trace export to "
                         "<run-dir>/trace.json")
    ap.add_argument("--profiler", action="store_true",
                    help="capture a jax.profiler device trace into "
                         "<run-dir>/jax_trace")
    ap.add_argument("--obs-cost", action="store_true",
                    help="record the compiled meta step's measured HBM / "
                         "peak-state numbers in the run manifest")
    ap.add_argument("--obs-health", action="store_true",
                    help="run-health watchdogs over the flushed metric "
                         "windows (obs.health): structured alerts in the "
                         "run log, fatal rules halt with a resumable "
                         "checkpoint")
    ap.add_argument("--obs-no-halt", action="store_true",
                    help="demote fatal health rules to warn: record "
                         "alerts, never stop the run")
    ap.add_argument("--obs-attribution", action="store_true",
                    help="measured-vs-modeled phase attribution rows "
                         "(obs.profile) recorded once before step 0")
    ap.add_argument("--resume", action="store_true",
                    help="restore the latest VERIFIED checkpoint from "
                         "--checkpoint-dir (torn/corrupt snapshots are "
                         "skipped via the CRC sidecar chain; falls back "
                         "to the newest unverified one) and append to "
                         "the run log")
    ap.add_argument("--checkpoint-every", type=int, default=10,
                    help="checkpoint cadence in meta steps (with "
                         "--checkpoint-dir)")
    ap.add_argument("--checkpoint-keep", type=int, default=0,
                    help="retain only the last N verified snapshots "
                         "(0 = keep everything)")
    ap.add_argument("--chaos", action="store_true",
                    help="deterministic fault injection (repro.chaos): "
                         "run under the standard fault schedule sized to "
                         "--steps/--learners. NOTE: int-token LM batches "
                         "carry no float leaves, so the nan fault kind "
                         "perturbs nothing here — use crash/payload/"
                         "straggle/torn_save")
    ap.add_argument("--chaos-seed", type=int, default=0,
                    help="seed of the standard chaos schedule")
    ap.add_argument("--chaos-faults", default=None,
                    help="comma subset of the standard fault kinds "
                         "(crash,nan,payload,straggle,torn_save); "
                         "default all")
    ap.add_argument("--robust", default=None, choices=ROBUST_ESTIMATORS,
                    help="robust meta aggregation (repro.robust): replace "
                         "the learner-stack mean with a coordinate-wise "
                         "trimmed mean or median ('mean' keeps the plain "
                         "mean but still enables clip/score below)")
    ap.add_argument("--robust-trim", type=int, default=1,
                    help="learners trimmed from EACH end per coordinate "
                         "(trimmed estimator)")
    ap.add_argument("--robust-clip", type=float, default=0.0,
                    help="per-learner displacement norm clip at this "
                         "multiple of the trailing-median budget "
                         "(0 = no clipping)")
    ap.add_argument("--robust-clip-window", type=int, default=8,
                    help="trailing-median ring length (meta steps) the "
                         "clip budget is computed over")
    ap.add_argument("--robust-no-score", action="store_true",
                    help="disable per-learner anomaly scoring (on by "
                         "default when --robust is set)")
    ap.add_argument("--robust-quarantine-after", type=int, default=0,
                    help="inline quarantine: mask a learner out of "
                         "membership after this many consecutive "
                         "anomalous flush windows (0 = never; needs a "
                         "membership-capable topology)")
    ap.add_argument("--finite-guard", action="store_true",
                    help="in-step NaN/Inf barrier: poisoned learner "
                         "planes are reset to the broadcast global "
                         "params before the mix (MAvgConfig.finite_guard)")
    ap.add_argument("--supervise", action="store_true",
                    help="wrap the run in core.supervisor.Supervisor: "
                         "on a health halt / checkpoint-verify failure, "
                         "roll back to the last verified snapshot and "
                         "retry with recovery policies (requires "
                         "--checkpoint-dir)")
    ap.add_argument("--supervise-retries", type=int, default=3,
                    help="supervisor retry budget before "
                         "RecoveryExhausted")
    ap.add_argument("--supervise-quarantine", type=int, default=0,
                    help="probation window (meta steps) a suspect "
                         "learner is quarantined from membership after "
                         "rollback (0 = never)")
    ap.add_argument("--supervise-readmit", type=int, default=1,
                    help="quarantine hysteresis: clean probation windows "
                         "a quarantined learner must sit out before "
                         "readmission (total mask = window * this)")
    return ap.parse_args(argv)


def build(args):
    """Everything a run needs from parsed ``args``.

    Returns ``(cfg, loss_fn, make_trainer)``; ``make_trainer(plan)``
    builds a fresh Trainer for a ``core.supervisor.RecoveryPlan``."""
    cfg = get_config(args.arch)
    if not args.full:
        cfg = cfg.reduced()
    if cfg.input_mode != "tokens":
        raise SystemExit(
            f"{args.arch} uses stub-frontend inputs; use examples/ for it"
        )

    outer_comm = (
        CommConfig(scheme=args.outer_comm, k_frac=args.comm_k_frac,
                   error_feedback=not args.no_error_feedback)
        if args.outer_comm else None
    )
    group_k = (
        tuple(int(k) for k in args.group_k.split(","))
        if args.group_k else None
    )
    elastic = (
        ElasticConfig(period=args.elastic_period, drop_frac=args.elastic_drop,
                      seed=args.elastic_seed)
        if args.elastic_period > 0 else None
    )
    server = (
        AsyncConfig(
            staleness=args.async_staleness,
            step_time=(tuple(int(t) for t in args.async_profile.split(","))
                       if args.async_profile else ()),
            skew=args.async_skew, seed=args.async_seed,
            update=args.async_update, decay=args.async_decay,
        )
        if args.topology == "async" else None
    )
    chaos_cfg = None
    if args.chaos:
        from repro.chaos import STANDARD_KINDS, standard_chaos

        kinds = (
            tuple(k.strip() for k in args.chaos_faults.split(","))
            if args.chaos_faults else STANDARD_KINDS
        )
        unknown = set(kinds) - set(STANDARD_KINDS)
        if unknown:
            raise SystemExit(
                f"--chaos-faults: unknown kinds {sorted(unknown)}; choose "
                f"from {STANDARD_KINDS}"
            )
        chaos_cfg = standard_chaos(
            args.learners, args.steps, seed=args.chaos_seed, kinds=kinds
        )
    if args.supervise and not args.checkpoint_dir:
        raise SystemExit("--supervise needs --checkpoint-dir (the "
                         "verified rollback chain lives there)")

    robust = (
        RobustConfig(
            estimator=args.robust, trim=args.robust_trim,
            clip_mult=args.robust_clip,
            clip_window=args.robust_clip_window,
            score=not args.robust_no_score,
            quarantine_after=args.robust_quarantine_after,
        )
        if args.robust is not None else None
    )

    def make_mcfg(momentum_scale: float = 1.0) -> MAvgConfig:
        return MAvgConfig(
            algorithm=args.algorithm, num_learners=args.learners,
            k_steps=args.k, learner_lr=args.lr,
            momentum=args.momentum * momentum_scale,
            compute_dtype=args.compute_dtype,
            # without a mesh one device holds every learner: running them
            # in turn keeps one learner's program and activations, not L
            sequential_learners=args.mesh == "none",
            finite_guard=args.finite_guard,
            robust=robust,
            comm=CommConfig(scheme=args.comm, k_frac=args.comm_k_frac,
                            error_feedback=not args.no_error_feedback),
            topology=TopologyConfig(
                kind=args.topology, groups=args.groups,
                outer_every=args.outer_every,
                outer_momentum=args.outer_momentum,
                graph=args.gossip_graph, outer_comm=outer_comm,
                group_k=group_k, elastic=elastic, server=server,
            ),
        )

    def loss_fn(params, batch):
        return model_api.loss_fn(params, cfg, batch)

    mesh = state_sh = None
    batch_fn = lm_batch_fn(cfg, args.learners, args.k, args.batch, args.seq)
    if args.mesh == "host":
        from repro.launch.mesh import make_host_mesh
        from repro.launch.specs import state_shardings

        mesh = make_host_mesh()
        n = mesh.shape["data"]
        if args.learners % n:
            raise SystemExit(
                f"--mesh host: --learners {args.learners} does not divide "
                f"over {n} chips"
            )
        state_sh = state_shardings(cfg, make_mcfg(), mesh)
        batch_sh = NamedSharding(mesh, P("data"))
        lm_batches = batch_fn
        batch_fn = lambda rng, step: jax.device_put(
            lm_batches(rng, step), batch_sh
        )

    def make_trainer(plan) -> Trainer:
        tcfg = TrainConfig(
            model=cfg, mavg=make_mcfg(plan.momentum_scale),
            batch_per_learner=args.batch, seq_len=args.seq,
            meta_steps=args.steps, checkpoint_dir=args.checkpoint_dir,
            checkpoint_every=(
                args.checkpoint_every if args.checkpoint_dir else 0
            ),
            checkpoint_keep=args.checkpoint_keep,
            chaos=chaos_cfg, data_salt=plan.data_salt,
            obs=ObsConfig(sink=args.obs_sink, run_dir=args.run_dir,
                          trace=args.trace, profiler=args.profiler,
                          cost_analysis=args.obs_cost,
                          health=args.obs_health,
                          health_halt=not args.obs_no_halt,
                          attribution=args.obs_attribution),
        )
        return Trainer(
            tcfg,
            loss_fn,
            init_params_fn=lambda rng: model_api.init_params(rng, cfg),
            batch_fn=batch_fn,
            lr_schedule=warmup_cosine(args.lr * plan.lr_scale, 5,
                                      args.steps),
            mesh=mesh,
            state_shardings=state_sh,
        )

    return cfg, loss_fn, make_trainer


def main(argv=None) -> None:
    from repro.launch.compile_cache import enable_compile_cache

    args = parse_args(argv)
    enable_compile_cache()
    cfg, loss_fn, make_trainer = build(args)

    if args.supervise:
        from repro.core.supervisor import (
            RecoveryPolicy,
            Supervisor,
        )

        sup = Supervisor(
            make_trainer,
            target_steps=args.steps,
            checkpoint_dir=args.checkpoint_dir,
            policy=RecoveryPolicy(
                max_retries=args.supervise_retries,
                quarantine_steps=args.supervise_quarantine,
                readmit_clean_windows=args.supervise_readmit,
            ),
        )
        trainer, history = sup.run()
    else:
        from repro.core.supervisor import RecoveryPlan

        trainer = make_trainer(RecoveryPlan())
        if args.resume:
            from repro.checkpoint import (
                latest_checkpoint,
                latest_verified_checkpoint,
            )

            ckpt = (
                latest_verified_checkpoint(args.checkpoint_dir or "")
                or latest_checkpoint(args.checkpoint_dir or "")
            )
            if ckpt is None:
                raise SystemExit(
                    "--resume: no checkpoint in --checkpoint-dir"
                )
            trainer.restore(ckpt)
            print(f"resumed from {ckpt}")
        history = trainer.run()

    # held out: one global batch of sequences (32 at the defaults)
    eval_batch = lm_eval_set(cfg, n=args.learners * args.batch,
                             seq_len=args.seq)
    loss, _ = jax.jit(loss_fn)(unpack_params(trainer.state), eval_batch)
    print(f"\nfinal train loss {history[-1]['loss']:.4f}  "
          f"eval loss {float(loss):.4f}  "
          f"samples {history[-1]['samples']}")
    trainer.close()


if __name__ == "__main__":
    main()
