"""repro.obs — on-device training telemetry, phase tracing, structured
run sinks (DESIGN.md §11).

Three pieces, composable and individually optional:

* ``MetricsBuffer`` (metrics.py): a fixed-size device-side ring of
  per-step metric rows, written INSIDE the jitted meta step (donated, in
  place) and flushed to host with one bulk transfer per ``log_every``
  window — telemetry without extra host syncs.
* ``Sink`` (sink.py): where flushed records and the run manifest go —
  JSONL (canonical, append-on-resume), CSV, or in-memory. Every Trainer
  run and every bench emits the same record envelope.
* ``Tracer`` (trace.py): config-gated phase span timers with Chrome-trace
  export and ``jax.profiler`` hooks.

``run_manifest`` (manifest.py) is the shared run-identity record: config,
PackSpec hash, topology/reducer/elastic settings, jax/device info, and
optionally the measured compiled-program cost (roofline.hlo_cost).

Two judgment pieces sit on top:

* ``profile`` (profile.py): a steady-state timing harness (warmup,
  block_until_ready, median + IQR) and the measured-vs-modeled
  attribution join against ``roofline.hlo_cost`` (achieved HBM GB/s,
  % of a measured bandwidth bound), behind ``ObsConfig.attribution``.
* ``health`` (health.py): declarative run-health rules over flushed
  metric windows -> structured ``alert`` records, with fatal rules
  halting the Trainer on a resumable checkpoint (``HealthHalt``).
"""
from repro.obs.health import (
    DEFAULT_RULES,
    HealthHalt,
    HealthMonitor,
    HealthRule,
    make_monitor,
)
from repro.obs.manifest import (
    SCHEMA_VERSION,
    device_env,
    packspec_hash,
    run_manifest,
)
from repro.obs.metrics import MetricsBuffer, metric_keys, write_row
from repro.obs.profile import (
    Timing,
    attribution_row,
    measured_peak_gbps,
    profile_fn,
    profile_phases,
    steady_timeit,
)
from repro.obs.sink import (
    SINKS,
    CsvSink,
    JsonlSink,
    MemorySink,
    Sink,
    make_sink,
)
from repro.obs.trace import Tracer

__all__ = [
    "DEFAULT_RULES",
    "SCHEMA_VERSION",
    "SINKS",
    "CsvSink",
    "HealthHalt",
    "HealthMonitor",
    "HealthRule",
    "JsonlSink",
    "MemorySink",
    "MetricsBuffer",
    "Sink",
    "Timing",
    "Tracer",
    "attribution_row",
    "device_env",
    "make_monitor",
    "make_sink",
    "measured_peak_gbps",
    "metric_keys",
    "packspec_hash",
    "profile_fn",
    "profile_phases",
    "run_manifest",
    "steady_timeit",
    "write_row",
]
