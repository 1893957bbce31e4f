"""Steady-state profiler attribution: measured wall-clock joined against
the modeled roofline cost (DESIGN.md §11, "Measured performance").

Every timing number this repo reports flows through ONE harness so the
methodology is uniform and stated once:

* **steady state** — ``warmup`` untimed calls first, so compilation,
  autotuning and allocator warm-up never leak into a reported number;
* **dispatch discipline** — each timed call is closed with
  ``jax.block_until_ready`` on its outputs, so what is measured is
  device completion, not async enqueue time;
* **median-of-N with IQR** — the reported statistic is the median over
  ``iters`` repeats with the interquartile range as the noise bar
  (means are garbage under scheduler jitter; a stddev assumes a
  symmetric distribution wall-clocks don't have).

The *attribution* join is the judgment half: a measured median on its
own says nothing about whether a kernel is fast. Joining it against the
compiled program's modeled HBM bytes (``roofline.hlo_cost.jit_cost``)
yields achieved GB/s, and dividing by a measured peak bandwidth
(``measured_peak_gbps`` — a jitted triad on this very machine, not a
datasheet constant) yields % of the roofline bound: the number that is
comparable across machines and across PRs, and the one
``tools/bench_compare.py`` gates on.

On CPU the Pallas kernels are timed through their jnp reference route
(interpret mode executes the kernel body block-by-block in Python — its
wall-clock is meaningless); on TPU the same harness times the native
``pallas_call``. The *methodology* is what is pinned by tests, not the
CPU numbers.
"""
from __future__ import annotations

import time
from dataclasses import dataclass

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# steady-state timing
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class Timing:
    """Median-of-N wall clock with IQR noise bar (seconds)."""

    median_s: float
    iqr_s: float
    n: int
    warmup: int
    times_s: tuple

    @property
    def median_us(self) -> float:
        return self.median_s * 1e6

    @property
    def iqr_us(self) -> float:
        return self.iqr_s * 1e6


def _quantile(sorted_xs, q: float) -> float:
    """Linear-interpolated quantile of an already-sorted list."""
    if len(sorted_xs) == 1:
        return sorted_xs[0]
    pos = q * (len(sorted_xs) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(sorted_xs) - 1)
    frac = pos - lo
    return sorted_xs[lo] * (1 - frac) + sorted_xs[hi] * frac


def steady_timeit(fn, *args, iters: int = 10, warmup: int = 2) -> Timing:
    """Time ``fn(*args)`` in steady state; returns a :class:`Timing`.

    The warmup calls absorb compilation and first-touch allocation; every
    timed call blocks on its outputs (``jax.block_until_ready``) so the
    measurement is dispatch->completion, not dispatch->return.
    """
    assert iters >= 1 and warmup >= 0, (iters, warmup)
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    xs = sorted(times)
    return Timing(
        median_s=_quantile(xs, 0.5),
        iqr_s=_quantile(xs, 0.75) - _quantile(xs, 0.25),
        n=iters,
        warmup=warmup,
        times_s=tuple(times),
    )


# ---------------------------------------------------------------------------
# measured peak bandwidth: the roofline ceiling of THIS machine
# ---------------------------------------------------------------------------

_PEAK_CACHE: dict[int, float] = {}


def measured_peak_gbps(nbytes: int = 1 << 26, *, refresh: bool = False,
                       iters: int = 5, warmup: int = 2) -> float:
    """Achievable memory bandwidth of the current default device, GB/s.

    A jitted saxpy over an ``nbytes``-sized f32 buffer (2 reads + 1
    write), timed with the same steady-state discipline as everything
    else. Cached per size — one measurement per process. Using a
    *measured* ceiling instead of a datasheet constant makes
    % -of-bound numbers meaningful on whatever machine the bench runs
    on (CPU container, TPU pod), and is the denominator
    ``attribution_row`` divides by.
    """
    if not refresh and nbytes in _PEAK_CACHE:
        return _PEAK_CACHE[nbytes]
    n = max(nbytes // 4, 1024)
    x = jnp.arange(n, dtype=jnp.float32)
    y = jnp.ones((n,), jnp.float32)
    saxpy = jax.jit(lambda x, y: x * 1.5 + y)
    t = steady_timeit(saxpy, x, y, iters=iters, warmup=warmup)
    gbps = 3.0 * n * 4 / t.median_s / 1e9  # 2 reads + 1 write
    _PEAK_CACHE[nbytes] = gbps
    return gbps


# ---------------------------------------------------------------------------
# measured-vs-modeled attribution
# ---------------------------------------------------------------------------


def attribution_row(op: str, timing: Timing, cost=None, *,
                    peak_gbps: float | None = None, extra=None) -> dict:
    """Join one measured :class:`Timing` against one modeled
    ``roofline.hlo_cost.JitCost`` into the canonical attribution record.

    Fields: the timing statistics, the modeled HBM bytes / flops of the
    compiled program, ``achieved_gbps`` (modeled bytes moved per measured
    second) and ``pct_of_bound`` (achieved bandwidth as a percentage of
    the measured peak — 100% means the kernel runs AT the machine's
    memory roofline; the gap is launch overhead, poor locality, or
    compute-boundness).
    """
    row = {
        "kind": "attribution",
        "op": op,
        "median_us": timing.median_us,
        "iqr_us": timing.iqr_us,
        "iters": timing.n,
        "warmup": timing.warmup,
        "backend": jax.default_backend(),
    }
    if cost is not None:
        achieved = cost.hbm_bytes / timing.median_s / 1e9
        row.update(
            modeled_hbm_bytes=float(cost.hbm_bytes),
            modeled_flops=float(cost.flops),
            achieved_gbps=achieved,
        )
        if peak_gbps:
            row.update(
                peak_gbps=float(peak_gbps),
                pct_of_bound=100.0 * achieved / peak_gbps,
            )
    if extra:
        row.update(extra)
    return row


def profile_fn(op: str, fn, *args, iters: int = 10, warmup: int = 2,
               peak_gbps: float | None = None, extra=None) -> dict:
    """Measure a jittable ``fn(*args)`` AND model it, in one call.

    Compiles ``fn`` twice on purpose: once through ``jit_cost`` (AOT
    lower/compile for the modeled HBM bytes — nothing is executed) and
    once for the timed steady-state loop. Returns the attribution row.
    """
    from repro.roofline.hlo_cost import jit_cost

    cost = jit_cost(fn, *args)
    timing = steady_timeit(jax.jit(fn), *args, iters=iters, warmup=warmup)
    return attribution_row(op, timing, cost, peak_gbps=peak_gbps, extra=extra)


# ---------------------------------------------------------------------------
# training-phase attribution: local phase vs meta mix vs whole step
# ---------------------------------------------------------------------------


def profile_phases(loss_fn, cfg, state, batches, lr=None, *, iters: int = 10,
                   warmup: int = 2, peak_gbps: float | None = None,
                   profiler_trace_dir: str | None = None) -> list[dict]:
    """Attribution rows for the two halves of one meta iteration.

    Times, with the shared steady-state discipline, (a) the whole jitted
    meta step, (b) the local phase alone (K-step scan over all learners)
    and, for the averaging algorithms, (c) the meta mix alone
    (``topology.mix`` on the current state's planes) — each joined
    against its own compiled-HLO modeled cost. The rows ride the same
    sink envelope as step records (``kind: attribution``) and are what
    ``pack_bench`` surfaces per config.

    Profiling uses FUNCTIONAL (non-donated) step instances: a donated
    step kills its input buffers on first dispatch, and a timing loop
    re-feeds the same arguments every iteration. Numerics are identical
    (donation is pure aliasing), so the attribution transfers.

    ``profiler_trace_dir``: optionally capture a ``jax.profiler`` device
    trace of one extra whole-step call into this directory (best-effort;
    the Chrome-trace-compatible xplane export lands next to the PR 6
    span traces).
    """
    from repro.core.meta import _local_phase, make_meta_step
    from repro.topology import make_topology

    lr = jnp.float32(cfg.learner_lr) if lr is None else lr
    # every algorithm now routes its meta phase through a Topology
    # (eamsgd/downpour are aliases onto the async server), so the
    # meta_mix row is always attributable
    topology = make_topology(cfg, None)

    step_fn = make_meta_step(loss_fn, cfg, topology=topology)

    def whole_step(s, b, l):
        return step_fn(s, b, lr=l)

    def local_phase(s, b, l):
        steps = topology.local_steps(s.topo, s.step)
        return _local_phase(loss_fn, s.learners, s.local_momentum, b, cfg,
                            l, steps=steps, spec=s.spec)

    def meta_mix(s):
        return topology.mix(s.learners, s.global_params, s.momentum,
                            s.comm_residual, s.topo, step=s.step)

    targets = [
        ("phase:step", whole_step, (state, batches, lr)),
        ("phase:local", local_phase, (state, batches, lr)),
        ("phase:meta_mix", meta_mix, (state,)),
    ]

    rows = [
        profile_fn(op, fn, *args, iters=iters, warmup=warmup,
                   peak_gbps=peak_gbps,
                   extra={"algorithm": cfg.algorithm,
                          "topology": cfg.topology.kind})
        for op, fn, args in targets
    ]

    if profiler_trace_dir:
        from repro.obs.trace import Tracer

        t = Tracer(enabled=True)
        t.profiler_start(profiler_trace_dir)
        try:
            jax.block_until_ready(jax.jit(whole_step)(state, batches, lr))
        finally:
            t.profiler_stop()
    return rows
