"""High-level training driver tying together model, data, meta-optimizer,
telemetry, checkpointing and (optionally) a device mesh.

Given a ``mesh`` and ``state_shardings`` (``repro.launch.specs``), the
initial state is placed on them and the jitted step keeps it there (the
learner axis sharded over the mesh's data axes — ``launch/train.py
--mesh host`` builds one over the chips of a host); without them the same
jitted program runs on the default device.

Telemetry (``repro.obs``, DESIGN.md §11): every per-step scalar the meta
step emits is written into an on-device MetricsBuffer ring *inside* the
jitted step, so the host never touches a metric between ``log_every``
boundaries — one bulk ``device_get`` per flush window is the only sync.
Flushed records (plus host-side wall-clock throughput) land in
``self.history`` and, when ``TrainConfig.obs`` selects a sink, in a
structured run log under a per-run manifest.
"""
from __future__ import annotations

import contextlib
import os
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from repro.checkpoint import load_state, save_state
from repro.configs.base import MAvgConfig, TrainConfig
from repro.core.meta import init_state, make_meta_step
from repro.obs import (
    HealthHalt,
    MetricsBuffer,
    Tracer,
    make_monitor,
    make_sink,
    metric_keys,
    run_manifest,
    write_row,
)

# argnum of the MetricsBuffer ring in the fused ``step(state, batches, lr,
# mbuf, mrow)`` signature — donated unconditionally (the caller never
# re-reads a pre-step ring; see launch/specs.py donate_extra)
_RING_ARGNUM = 3


class Trainer:
    def __init__(
        self,
        train_cfg: TrainConfig,
        loss_fn: Callable,
        init_params_fn: Callable,
        batch_fn: Callable,  # (rng, step) -> batches (L, K, B, ...)
        lr_schedule: Optional[Callable] = None,
        mesh=None,
        state_shardings=None,
    ):
        self.cfg = train_cfg
        self.mcfg: MAvgConfig = train_cfg.mavg
        self.obs_cfg = train_cfg.obs
        self.loss_fn = loss_fn
        self.batch_fn = batch_fn
        self.lr_schedule = lr_schedule
        self.mesh = mesh
        self._state_shardings = state_shardings if mesh is not None else None

        # fault injection (repro.chaos, DESIGN.md §13): compile the
        # schedule once and thread each layer's injector to its layer —
        # the config transform (crash -> elastic membership, straggle ->
        # async profile) BEFORE the topology is built, the batch poisoner
        # around batch_fn, the payload corruptor into the jitted step,
        # and save faults into the checkpoint writer (see run()). With
        # chaos None every one of these is the untouched original object.
        self._chaos_schedule = None
        chaos_corruptor = None
        if train_cfg.chaos is not None:
            from repro.chaos import (
                FaultSchedule,
                PayloadCorruptor,
                apply_chaos,
                wrap_batch_fn,
            )

            self.mcfg = apply_chaos(
                self.mcfg, train_cfg.chaos, salt=train_cfg.data_salt
            )
            self._chaos_schedule = FaultSchedule(
                train_cfg.chaos, self.mcfg.num_learners,
                salt=train_cfg.data_salt,
            )
            self.batch_fn = wrap_batch_fn(batch_fn, self._chaos_schedule)
            if self._chaos_schedule.any_payload_faults:
                chaos_corruptor = PayloadCorruptor(self._chaos_schedule)

        rng = jax.random.PRNGKey(train_cfg.seed)
        self.data_rng, init_rng = jax.random.split(rng)
        if train_cfg.data_salt:
            # supervisor retries redraw the data stream (the transient
            # non-sticky faults already dropped out of the schedule above)
            self.data_rng = jax.random.fold_in(
                self.data_rng, train_cfg.data_salt
            )
        params = init_params_fn(init_rng)
        # one topology instance serves state init, the jitted step, and
        # the host-side effective-samples accounting (work_completed) —
        # async profiles complete fewer K-step blocks per tick than L
        from repro.topology import make_topology

        self._topology = make_topology(self.mcfg)
        if self._state_shardings is None:
            self.state = init_state(params, self.mcfg,
                                    topology=self._topology)
        else:
            # built straight into its shardings: no device ever holds
            # the whole learner stack
            self.state = jax.jit(
                lambda p: init_state(p, self.mcfg, topology=self._topology),
                out_shardings=self._state_shardings,
            )(params)
        self._step_fn = make_meta_step(
            loss_fn, self.mcfg, topology=self._topology,
            chaos=chaos_corruptor,
        )

        # telemetry is built lazily at the first run() iteration: the
        # metric-key set is only known from the step's abstract output
        # (jax.eval_shape — no compile), and the ring must exist before
        # the first fused dispatch
        self._mb: Optional[MetricsBuffer] = None
        self._fused = None
        self._sink = None
        self.manifest: Optional[dict] = None
        self.tracer = Tracer(self.obs_cfg.trace)
        self._restored = False
        self.history: list[dict] = []
        # health watchdogs (obs.health): consume only flushed host
        # floats, so a healthy run is bitwise identical with them on
        self._monitor = (
            make_monitor(halt=self.obs_cfg.health_halt)
            if self.obs_cfg.health else None
        )
        self.attribution: list[dict] = []
        # inline quarantine (repro.robust, DESIGN.md §14): host-side
        # streak counter over the flushed per-learner anomaly scores —
        # a persistently-anomalous learner is masked out of membership
        # right here, without a HealthHalt/supervisor round-trip
        self.robust_records: list[dict] = []
        self.quarantined: dict[int, int] = {}  # learner -> quarantine step
        self._anomaly_streak = None

    # ------------------------------------------------------------------
    # telemetry assembly (lazy, once per Trainer)
    # ------------------------------------------------------------------

    def _init_obs(self, batches, lr):
        """Build the metric ring, fused jitted step, manifest and sink.

        The fused step writes the step's metric scalars into row ``mrow``
        of the donated ring *inside* the jitted program:

            step(state, batches, lr, mbuf, mrow) -> (state', mbuf')

        Metrics therefore reach the host exclusively through
        ``MetricsBuffer.flush`` (one bulk device_get per log window) —
        there is no per-step host read to accidentally sync on, and under
        ``mcfg.donate`` the metric write adds zero copies: both the state
        and the ring are updated in place.
        """
        obs = self.obs_cfg

        def fused(state, b, lr_, mbuf, mrow):
            state, metrics = self._step_fn(state, b, lr=lr_)
            mbuf = write_row(mbuf, mrow, metrics, self._mkeys)
            return state, mbuf

        # abstract eval discovers the metric keys without compiling
        _, metrics_sds = jax.eval_shape(
            lambda s, b, l: self._step_fn(s, b, lr=l), self.state, batches, lr
        )
        self._mkeys = metric_keys(metrics_sds)
        capacity = obs.buffer_capacity or max(self.cfg.log_every, 1)
        self._mb = MetricsBuffer(self._mkeys, capacity)
        rep = None
        if self._state_shardings is not None:
            # the ring lives replicated on the mesh from the first step:
            # a single-device ring would come back mesh-sharded and the
            # second step would compile the program again
            rep = NamedSharding(self.mesh, PartitionSpec())
            self._mb.buf = jax.device_put(self._mb.buf, rep)

        from repro.launch.specs import meta_step_jit_kwargs

        kwargs = meta_step_jit_kwargs(
            self.mcfg,
            self._state_shardings,
            n_extra_args=4,
            donate_extra=(_RING_ARGNUM,),
            replicated=rep,
        )
        self._fused = jax.jit(fused, **kwargs)

        jc = None
        if obs.cost_analysis:
            from repro.roofline.hlo_cost import jit_cost

            # the bare (state, batches, lr) step, not the fused one: the
            # metric ring is telemetry, not part of the training program
            # whose HBM/peak-state cost the manifest records. Asked for
            # and failing is an error, not a manifest without the numbers.
            jc = jit_cost(
                lambda s, b, l: self._step_fn(s, b, lr=l),
                self.state, batches, lr,
                **({"donate_argnums": (0,)} if self.mcfg.donate else {}),
            )
        self.manifest = run_manifest(
            train_cfg=self.cfg,
            mcfg=self.mcfg,
            spec=getattr(self.state, "spec", None),
            jit_cost=jc,
        )
        if obs.sink != "none" and self._sink is None:
            self._sink = make_sink(
                obs.sink, obs.run_dir, resume=self._restored
            )
            self._sink.open_run(self.manifest)
        if obs.attribution:
            # measured-vs-modeled phase attribution, once before step 0:
            # functional (non-donated) copies of the step/phases are
            # steady-state timed and joined against their compiled-HLO
            # modeled bytes — the training state is untouched
            from repro.obs import measured_peak_gbps, profile_phases

            self.attribution = profile_phases(
                self.loss_fn, self.mcfg, self.state, batches, lr,
                iters=5, warmup=2, peak_gbps=measured_peak_gbps(),
            )
            if self._sink is not None:
                for row in self.attribution:
                    self._sink.append(row)

    def compiled_step(self):
        """The compiled executable of the fused step for the current
        state (``.as_text()``, ``.memory_analysis()``). After ``run`` this
        is the program the steps ran: lowering hits jit's cache, and the
        lowered computation keeps its compiled executable."""
        assert self._fused is not None, "run() at least one step first"
        step = int(self.state.step)
        # inputs made as run() makes them, mesh context included (it is
        # part of their abstract types, and so of jit's cache key)
        with self._mesh_context():
            batches = self.batch_fn(
                jax.random.fold_in(self.data_rng, step), step
            )
            lr = (self.lr_schedule(step) if self.lr_schedule
                  else jnp.float32(self.mcfg.learner_lr))
            return self._fused.lower(
                self.state, batches, lr, self._mb.buf, self._mb.row_index()
            ).compile()

    def _mesh_context(self):
        """The mesh in context while the step is traced: kernels that XLA
        cannot partition wrap themselves in ``shard_map`` over it."""
        if self.mesh is None:
            return contextlib.nullcontext()
        return jax.set_mesh(self.mesh)

    # ------------------------------------------------------------------
    # driving loop
    # ------------------------------------------------------------------

    def run(self, meta_steps: Optional[int] = None, log=print):
        """Drive ``meta_steps`` jitted steps.

        Metrics stay on-device until a ``log_every`` boundary (or the end
        of the run): the fused step accumulates them into the MetricsBuffer
        ring, and only the boundary pays one bulk device_get — the
        in-between steps are enqueued back-to-back with zero host syncs.
        ``history`` holds plain float dicts afterwards, now including
        wall-clock throughput (``meta_steps_per_sec``, ``samples_per_sec``,
        ``elapsed_s``) computed host-side per flush window.

        Donation contract (``MAvgConfig.donate``): the state handed to
        the fused step is dead the moment the call is dispatched — its
        planes are aliased into the returned state's, and the metric ring
        is likewise donated and rebound every step. Everything in this
        loop therefore works off RETURNED values: the step counter is
        read once before any dispatch, metrics are step outputs flushed
        from the returned ring, the checkpoint cadence is host arithmetic
        on python ints, and ``save_state`` snapshots a returned state
        (never an input a later dispatch may have consumed).
        """
        n = meta_steps if meta_steps is not None else self.cfg.meta_steps
        run_t0 = time.time()
        self._last_flush_t = run_t0
        # samples per completed K-step block; the topology says how many
        # blocks have completed through a given meta step (async learners
        # fire on their own clocks, so blocks/tick varies)
        samples_per_block = self.mcfg.k_steps * self.cfg.batch_per_learner
        samples_per_meta = self.mcfg.num_learners * samples_per_block

        def flush():
            if self._mb is None or not self._mb.count:
                return
            with self.tracer.span("obs.host_flush"):
                recs = self._mb.flush()
            now = time.time()
            dt = max(now - self._last_flush_t, 1e-9)
            self._last_flush_t = now
            msps = len(recs) / dt
            robust_rows = self._extract_robust(recs)
            for r in recs:
                s = r["meta_step"]
                r["samples"] = (
                    self._topology.work_completed(s) * samples_per_block
                )
                r["meta_steps_per_sec"] = msps
                r["samples_per_sec"] = msps * samples_per_meta
                r["elapsed_s"] = now - run_t0
                self.history.append(r)
            self._observe_robust(robust_rows)
            alerts = (
                self._monitor.observe(recs) if self._monitor is not None
                else ()
            )
            if self._sink is not None:
                with self.tracer.span("obs.sink_append"):
                    for r in recs:
                        self._sink.append(r)
                    for rb in robust_rows:
                        self._sink.append(rb)
                    for a in alerts:
                        self._sink.append(a)
                    self._sink.flush()

        def maybe_halt(step):
            # raised ONLY from in-loop flush boundaries (never from the
            # finally-flush — a halt must not mask a real traceback or
            # fire after the loop already ended)
            if self._monitor is None or not self._monitor.halt_requested:
                return
            alert = self._monitor.halt_alert
            ckpt_dir = self.cfg.checkpoint_dir or (
                os.path.join(self.obs_cfg.run_dir, "halt_ckpt")
                if self.obs_cfg.run_dir else None
            )
            path = None
            if ckpt_dir:
                with self.tracer.span("obs.checkpoint_io"):
                    path = save_state(
                        ckpt_dir, self.state, step + 1,
                        manifest=self.manifest,
                    )
            raise HealthHalt(alert, path)

        # trace/profiler lifecycle is exception-safe: the session closes
        # open spans, stops the profiler and exports the Chrome trace on
        # ANY exit — including the final flush below, whose spans land in
        # the exported file
        run_dir = self.obs_cfg.run_dir
        export_path = (
            os.path.join(run_dir, "trace.json")
            if self.obs_cfg.trace and run_dir else None
        )
        profiler_dir = (
            os.path.join(run_dir, "jax_trace")
            if self.obs_cfg.profiler and run_dir else None
        )
        with self.tracer.session(export_path, profiler_dir), \
                self._mesh_context():
            with self.tracer.span("obs.step_read"):
                start = int(self.state.step)  # the only pre-loop host sync
            try:
                for i in range(n):
                    step = start + i
                    with self.tracer.step(step):
                        with self.tracer.span("obs.batch"):
                            rng = jax.random.fold_in(self.data_rng, step)
                            batches = self.batch_fn(rng, step)
                        with self.tracer.span("obs.lr"):
                            lr = (
                                self.lr_schedule(step)
                                if self.lr_schedule
                                else jnp.float32(self.mcfg.learner_lr)
                            )
                        if self._mb is None:
                            self._init_obs(batches, lr)
                        if self._mb.full:  # ring smaller than the log window
                            flush()
                            maybe_halt(step - 1)
                        with self.tracer.span("obs.dispatch"):
                            self.state, ring = self._fused(
                                self.state, batches, lr,
                                self._mb.buf, self._mb.row_index(),
                            )
                        self._mb.note(step, ring)
                        if log and (step % self.cfg.log_every == 0):
                            flush()
                            maybe_halt(step)
                            m = self.history[-1]
                            log(
                                f"[{self.mcfg.algorithm}] meta_step={step} "
                                f"loss={m['loss']:.4f} "
                                f"gnorm={m.get('grad_norm', 0):.3f} "
                                f"{m['meta_steps_per_sec']:.2f} steps/s "
                                f"{m['samples_per_sec']:.0f} samples/s "
                                f"({time.time() - run_t0:.1f}s)"
                            )
                        if (
                            self.cfg.checkpoint_dir
                            and self.cfg.checkpoint_every
                            and (step + 1) % self.cfg.checkpoint_every == 0
                        ):
                            fault = (
                                self._chaos_schedule.save_fault(step + 1)
                                if self._chaos_schedule is not None else None
                            )
                            with self.tracer.span("obs.checkpoint_io"):
                                save_state(
                                    self.cfg.checkpoint_dir, self.state,
                                    step + 1,
                                    manifest=self.manifest,
                                    keep=self.cfg.checkpoint_keep,
                                    fault=fault,
                                )
                flush()  # the final (possibly partial) log window
                maybe_halt(start + n - 1)
            finally:
                flush()  # metrics of completed steps survive an interrupt
                if self._sink is not None:
                    self._sink.flush()
        return self.history

    # ------------------------------------------------------------------
    # robust telemetry + inline quarantine (repro.robust, DESIGN.md §14)
    # ------------------------------------------------------------------

    def _extract_robust(self, recs):
        """Pop the ``robust_*`` metric scalars out of the flushed step
        records and repackage them as ``robust`` records (telemetry
        schema v4) — one per meta step that carried them. Step rows stay
        on the v3 step schema; the robust rows ride the same sink."""
        from repro.robust import ROBUST_METRIC_PREFIX as P

        rows = []
        for r in recs:
            if not any(k.startswith(P) for k in r):
                continue
            rb = {
                "kind": "robust",
                "meta_step": r["meta_step"],
                "clipped_learners": r.pop(P + "clipped_learners", 0.0),
                "clip_budget": r.pop(P + "clip_budget", 0.0),
                "anomaly_score": r.pop(P + "anomaly_score", 0.0),
                "trim_fraction": r.pop(P + "trim_fraction", 0.0),
            }
            scores = []
            while f"{P}score_{len(scores)}" in r:
                scores.append(r.pop(f"{P}score_{len(scores)}"))
            if scores:
                rb["scores"] = scores
            for k in [k for k in r if k.startswith(P)]:
                r.pop(k)
            rows.append(rb)
        self.robust_records.extend(rows)
        return rows

    def _observe_robust(self, rows):
        """The inline quarantine controller: a learner whose windowed
        mean anomaly score exceeds ``score_ratio`` x the peer median for
        ``quarantine_after`` consecutive flush windows is masked out of
        the membership schedule on the spot — graceful degradation with
        no HealthHalt round-trip and no rollback (the robust mix already
        bounded its influence; quarantine just stops paying its wire and
        compute). Needs a membership-capable run (an elastic schedule or
        chaos crash faults) — quietly inert otherwise."""
        import numpy as np

        rcfg = self.mcfg.robust
        if rcfg is None or rcfg.quarantine_after <= 0:
            return
        sc = [row["scores"] for row in rows if "scores" in row]
        if not sc:
            return
        mean = np.asarray(sc, np.float64).mean(axis=0)  # (L,)
        med = float(np.median(mean))
        anomalous = mean > rcfg.score_ratio * max(med, 1e-30)
        if self._anomaly_streak is None:
            self._anomaly_streak = np.zeros(mean.shape[0], np.int64)
        self._anomaly_streak = np.where(
            anomalous, self._anomaly_streak + 1, 0
        )
        hit = [
            j for j in range(mean.shape[0])
            if self._anomaly_streak[j] >= rcfg.quarantine_after
            and j not in self.quarantined
        ]
        topo = self.state.topo
        if not hit or not (isinstance(topo, dict) and "membership" in topo):
            return
        m = np.asarray(topo["membership"], np.float32).copy()
        m[:, hit] = 0.0
        if (m.sum(axis=1) < 1.0).any():
            return  # never quarantine away the last present learner(s)
        step = int(rows[-1]["meta_step"])
        self.set_membership(m)
        for j in hit:
            self.quarantined[j] = step
        rows[-1]["quarantined"] = sorted(self.quarantined)

    def restore(self, path):
        self.state = load_state(path, self.state)
        # a sink opened after restore appends to the existing run log
        # instead of truncating it (resume continues the same run)
        self._restored = True

    def set_membership(self, membership):
        """Replace the elastic membership schedule in-state (the
        supervisor's quarantine lever, DESIGN.md §13): new (period, L)
        0/1 rows are swapped into ``MetaState.topo["membership"]`` —
        masked through the stochastic-complement rewiring like any other
        absence — and the topology's host-side mirror (the async server's
        effective-work replay) is reset to match. Only valid on a run
        that has a membership schedule (an elastic config or chaos crash
        faults); must preserve the schedule's shape."""
        import numpy as np

        topo = self.state.topo
        if not (isinstance(topo, dict) and "membership" in topo):
            raise ValueError(
                "set_membership needs a run with an elastic membership "
                "schedule (TopologyConfig.elastic or chaos crash faults)"
            )
        m = np.asarray(membership, np.float32)
        old = np.asarray(topo["membership"])
        if m.shape != old.shape:
            raise ValueError(
                f"membership shape {m.shape} != schedule shape {old.shape}"
            )
        if (m.sum(axis=1) < 1.0).any():
            raise ValueError(
                "quarantine membership leaves a row with no learner present"
            )
        from dataclasses import replace as _dc_replace

        new_topo = dict(topo)
        new_topo["membership"] = jnp.asarray(m)
        self.state = _dc_replace(self.state, topo=new_topo)
        if getattr(self._topology, "membership", None) is not None:
            self._topology.membership = m
            if hasattr(self._topology, "_sim_clock"):
                # invalidate the async server's completed-work replay —
                # it re-simulates from tick 0 under the new schedule
                self._topology._sim_clock = self._topology.start_clock.copy()
                self._topology._sim_t = 0
                self._topology._sim_cum = []

    def emit(self, record: dict):
        """Append one structured record to the run's telemetry sink (the
        supervisor's fault/recovery records ride the same log as the
        step rows). No-op when no sink is configured/open."""
        if self._sink is not None:
            self._sink.append(record)
            self._sink.flush()

    def close(self):
        """Flush and close the telemetry sink (idempotent)."""
        if self._sink is not None:
            self._sink.close()
            self._sink = None
