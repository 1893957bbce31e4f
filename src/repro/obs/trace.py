"""Phase tracing: config-gated span timers with Chrome-trace export.

Every host step of the training loop is timed: each iteration is one
``obs.meta_step`` span (``Tracer.step``) holding the batch draw
(``obs.batch``), the learning-rate schedule (``obs.lr``), step dispatch
(``obs.dispatch``: local phase + meta mix enqueue), the host flush
(``obs.host_flush``, the one sync per ``log_every`` window), sink writes
(``obs.sink_append``) and checkpoint I/O (``obs.checkpoint_io``); the
run's one step-counter read before the loop is ``obs.step_read``, and
the whole run, these and the final flush, is ``obs.run``.
``Tracer.span`` wraps each in a wall-clock timer plus a
``jax.profiler.TraceAnnotation`` (``StepTraceAnnotation`` for the step)
so the spans also show up inside a device profile when one is being
captured (``profiler_start``/``profiler_stop`` drive
``jax.profiler.start_trace`` around the run). The on-device split comes
from ``jax.named_scope`` annotations, which label the HLO itself:
``obs.local_phase`` and ``obs.meta_mix`` in ``core.meta.meta_step``,
inside the local phase ``obs.learner_update`` (core/meta.py),
``obs.mlstm`` and ``obs.slstm`` (models/xlstm.py) and ``obs.head``
(models/layers.py).

Disabled tracers cost one predicate per span — safe to leave in hot
paths. ``export_chrome_trace`` writes the collected spans in the Chrome
``chrome://tracing`` / Perfetto JSON event format, no profiler plugin
needed.

Tracing is exception-safe: ``session`` is the context-manager form the
Trainer wraps its whole run in — on ANY exit (normal, KeyboardInterrupt,
a crash mid-span) it closes still-open spans (recorded with an
``interrupted`` mark), stops a live device profile, and flushes the
Chrome-trace file, so a crashed run still yields a loadable trace of
everything up to the failure.
"""
from __future__ import annotations

import json
import os
import time
from contextlib import contextmanager


class Tracer:
    def __init__(self, enabled: bool = False):
        self.enabled = bool(enabled)
        self.events: list[tuple[str, float, float]] = []  # (name, t0, dur) s
        self._t0 = time.perf_counter()
        self._profiling = False
        # spans entered but not yet exited, as (name, t0) — a crash inside
        # a span unwinds through span()'s finally, but a crash BETWEEN the
        # profiler annotation setup and it, or a generator that is never
        # resumed (GC'd mid-suspend), leaves entries here for
        # close_open_spans to finalize
        self._open: list[tuple[str, float]] = []
        self.interrupted: list[str] = []  # names closed abnormally

    @contextmanager
    def span(self, name: str, step_num: int | None = None):
        """Time a phase; no-op (one branch) when disabled. With
        ``step_num`` the span marks that step in a device profile."""
        if not self.enabled:
            yield
            return
        import jax

        annotation = (
            jax.profiler.TraceAnnotation(name) if step_num is None
            else jax.profiler.StepTraceAnnotation(name, step_num=step_num)
        )
        t0 = time.perf_counter()
        entry = (name, t0)
        self._open.append(entry)
        try:
            with annotation:
                yield
        finally:
            if entry in self._open:
                self._open.remove(entry)
            self.events.append((name, t0 - self._t0, time.perf_counter() - t0))

    def step(self, step: int):
        """The span ``obs.meta_step`` around one iteration of the training
        loop, marked as step ``step`` in a device profile; no-op (one
        branch) when disabled."""
        return self.span("obs.meta_step", step_num=step)

    def close_open_spans(self) -> list[str]:
        """Finalize every still-open span at the current wall clock.

        Normally a no-op (span()'s finally pops the stack); after an
        abnormal unwind it records each orphan as a complete event ending
        now and returns the closed names (also kept in ``interrupted``).
        """
        now = time.perf_counter()
        closed = []
        while self._open:
            name, t0 = self._open.pop()
            self.events.append((name, t0 - self._t0, now - t0))
            closed.append(name)
        self.interrupted.extend(closed)
        return closed

    @contextmanager
    def session(self, export_path: str | None = None,
                profiler_dir: str | None = None):
        """Exception-safe tracing scope around a whole run, itself the
        span ``obs.run``.

        Enter: optionally starts a device profile into ``profiler_dir``.
        Exit — ALWAYS, crash included: closes open spans, stops the
        profiler, and (if ``export_path``) writes the Chrome trace, so
        whatever was recorded before a failure is loadable. Export
        errors are swallowed on the exception path only — telemetry must
        not mask the real traceback.
        """
        if profiler_dir:
            self.profiler_start(profiler_dir)
        ok = False
        try:
            with self.span("obs.run"):
                yield self
            ok = True
        finally:
            self.close_open_spans()
            self.profiler_stop()
            if self.enabled and export_path:
                try:
                    self.export_chrome_trace(export_path)
                except Exception:
                    if ok:  # pragma: no cover - export itself failed
                        raise

    # ------------------------------------------------------------------
    def export_chrome_trace(self, path: str) -> str:
        """Write spans as Chrome-trace JSON (load in chrome://tracing or
        https://ui.perfetto.dev). Timestamps in microseconds since the
        tracer was created, or since the device profile it started."""
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        events = [
            {
                "name": name,
                "ph": "X",  # complete event: begin + duration
                "ts": t0 * 1e6,
                "dur": dur * 1e6,
                "pid": 0,
                "tid": 0,
                "cat": "repro.obs",
            }
            for name, t0, dur in self.events
        ]
        with open(path, "w") as f:
            json.dump({"traceEvents": events, "displayTimeUnit": "ms"}, f)
        return path

    # ------------------------------------------------------------------
    def profiler_start(self, trace_dir: str) -> None:
        """Start a jax device profile into ``trace_dir`` (TensorBoard /
        xplane format, includes its own Chrome trace). A profile that was
        asked for and cannot start raises: a run that silently produced
        no trace would read as a traced one.

        The spans' clock is moved to the profile's origin, which the
        profiler sets as ``start_trace`` returns (its first call in a
        process first spends tens of ms setting up), so ``trace.json``
        and the profile lie side by side; spans already recorded are
        shifted onto the new clock."""
        import jax

        jax.profiler.start_trace(trace_dir)
        origin = time.perf_counter()
        self.events = [(name, t0 + self._t0 - origin, dur)
                       for name, t0, dur in self.events]
        self._t0 = origin
        self._profiling = True

    def profiler_stop(self) -> None:
        if not self._profiling:
            return
        try:
            import jax

            jax.profiler.stop_trace()
        except Exception:
            pass
        self._profiling = False
