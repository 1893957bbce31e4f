"""Robust-aggregation benchmark (repro.robust, DESIGN.md §14).

The acceptance experiment the subsystem exists for: under STICKY finite
payload corruption — a learner whose wire payloads are persistently
mis-scaled and bit-flipped, huge but finite, invisible to the in-step
finite guard — robust aggregation (trimmed mean + trailing-median norm
clip) must stay within 5% of the fault-free final loss at equal
effective samples with ZERO supervisor rollbacks, while the trusting
plain mean degrades badly. Graceful degradation, not detect-and-rollback.

Arms:

  fault_free      no chaos, robust off — the loss bar
  corrupt_mean    sticky finite corruption, plain mean — must degrade
                  (the threat is real; without this cell the 5% bound is
                  vacuous)
  corrupt_robust  same corruption, trimmed mean + norm clip + anomaly
                  scores, run under a Supervisor — within 5% of the bar
                  and zero recovery records
  robust_off      RobustConfig(mean, no clip, no score) vs robust=None —
                  final state must be BITWISE identical (the hooks cost
                  nothing when they don't act)

Prints ``robust,...`` CSV lines; ``--json PATH`` dumps every row.
``--smoke`` shrinks steps; tests/test_robust.py (R12) holds the
acceptance row at that size.
"""
from __future__ import annotations

import argparse
import os
import sys

if __package__ in (None, ""):  # `python benchmarks/robust_bench.py --smoke`
    _root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(_root, "src"))
    sys.path.insert(0, _root)

import numpy as np

from benchmarks.common import CLASSES, D_IN, HIDDEN
from repro.chaos import ChaosConfig, FaultSpec
from repro.configs.base import (
    MAvgConfig,
    ObsConfig,
    RobustConfig,
    TrainConfig,
)
from repro.core import RecoveryPolicy, Supervisor, Trainer
from repro.data import classif_batch_fn
from repro.models.simple import mlp_init, mlp_loss

P, K, MU, LR, BATCH = 4, 4, 0.7, 0.2, 16
BAD = P - 1  # the persistently-corrupt learner

ROBUST = RobustConfig(estimator="trimmed", trim=1, clip_mult=3.0,
                      clip_window=4, score=True)
INERT = RobustConfig(estimator="mean", clip_mult=0.0, score=False)


def _sticky_corruption(steps: int) -> ChaosConfig:
    """Learner BAD ships finite-but-corrupt payloads: a STUCK exponent
    bit (bit 29 flipped on one element of every payload, all run long —
    broken SerDes lane) plus a 3-step burst where the whole plane is
    scaled x12 (a mis-scaled wire payload). Both are huge-but-finite —
    invisible to the finite guard — and both are order-statistic /
    norm-budget outliers the robust mix can reject.

    Deliberately NOT in the schedule: a *persistent* full-plane scale.
    Scaling w = gp + d by m makes the displacement (m-1)*gp + m*d — a
    gp-ALIGNED vector whose per-coordinate values hide inside the benign
    spread on low-|gp| coordinates, so coordinate-wise trimming admits an
    O(spread) bias that momentum compounds into slow divergence. That
    failure mode needs the inline quarantine (membership-capable
    topologies, pinned in tests/test_robust.py) — bounding influence per
    step cannot fix a forever-biased learner (DESIGN.md §14)."""
    return ChaosConfig(seed=0, horizon=steps, faults=(
        FaultSpec("finite_bitflip", step=0, learner=BAD, duration=steps,
                  bit=29, sticky=True),
        FaultSpec("finite_scale", step=steps // 4, learner=BAD, duration=3,
                  magnitude=12.0, sticky=True),
    ))


def _make_trainer(steps, *, chaos=None, robust=None, guard=False, salt=0,
                  lr_scale=1.0, momentum_scale=1.0):
    mcfg = MAvgConfig(
        algorithm="mavg", num_learners=P, k_steps=K,
        learner_lr=LR * lr_scale, momentum=MU * momentum_scale,
        finite_guard=guard, robust=robust,
    )
    tcfg = TrainConfig(
        model=None, mavg=mcfg, batch_per_learner=BATCH, meta_steps=steps,
        seed=0, log_every=2, chaos=chaos, data_salt=salt,
        obs=ObsConfig(sink="none"),
    )
    return Trainer(
        tcfg, mlp_loss,
        init_params_fn=lambda rng: mlp_init(rng, D_IN, HIDDEN, CLASSES),
        batch_fn=classif_batch_fn(D_IN, CLASSES, P, K, BATCH),
    )


def _final_loss(history):
    tail = [r["loss"] for r in history[-5:]]
    return sum(tail) / len(tail)


def _state_finite(state) -> bool:
    planes = [state.global_params, state.momentum, state.learners]
    return all(
        bool(np.isfinite(np.asarray(p)).all()) for p in planes
        if p is not None
    )


def measured(quick: bool) -> list[dict]:
    steps = 16 if quick else 32
    rows: list[dict] = []

    # --- fault-free bar ---------------------------------------------------
    tr = _make_trainer(steps)
    base_hist = tr.run(log=None)
    base_loss = _final_loss(base_hist)
    base_samples = base_hist[-1]["samples"]
    tr.close()
    rows.append({
        "kind": "robust_measured", "cell": "fault_free",
        "final_loss": base_loss, "effective_samples": base_samples,
        "state_finite": _state_finite(tr.state),
    })

    def base_loss_at(samples):
        upto = (
            [r for r in base_hist if r["samples"] <= samples]
            or base_hist[:1]
        )
        return _final_loss(upto)

    chaos = _sticky_corruption(steps)

    # --- plain mean under sticky finite corruption: the threat is real ----
    tr = _make_trainer(steps, chaos=chaos, guard=True)
    mean_hist = tr.run(log=None)
    mean_loss = _final_loss(mean_hist)
    tr.close()
    mean_gap = mean_loss / base_loss_at(mean_hist[-1]["samples"])
    rows.append({
        "kind": "robust_measured", "cell": "corrupt_mean",
        "final_loss": mean_loss, "loss_vs_fault_free": mean_gap,
        "effective_samples": mean_hist[-1]["samples"],
    })

    # --- robust aggregation under the SAME corruption, supervised ---------
    def make_trainer(plan):
        return _make_trainer(
            steps, chaos=chaos, robust=ROBUST, guard=True,
            salt=plan.data_salt, lr_scale=plan.lr_scale,
            momentum_scale=plan.momentum_scale,
        )

    sup = Supervisor(make_trainer, target_steps=steps, checkpoint_dir=None,
                     policy=RecoveryPolicy(max_retries=2))
    tr, _ = sup.run(log=None)
    rob_loss = _final_loss(tr.history)
    rob_samples = tr.history[-1]["samples"]
    rollbacks = sum(1 for r in sup.records if r.get("kind") == "recovery")
    rob_finite = _state_finite(tr.state)
    n_robust_records = len(tr.robust_records)
    max_score = max(
        (max(rb.get("scores", [0.0])) for rb in tr.robust_records),
        default=0.0,
    )
    # the single-element stuck bit is below the anomaly noise floor on
    # quiet steps (by design — see _sticky_corruption); the pin is that
    # the MOST anomalous observation of the run fingers the bad learner
    scored = [rb for rb in tr.robust_records if "scores" in rb]
    anomalous_is_bad = bool(scored) and int(np.argmax(
        max(scored, key=lambda rb: max(rb["scores"]))["scores"]
    )) == BAD
    tr.close()
    rows.append({
        "kind": "robust_measured", "cell": "corrupt_robust",
        "final_loss": rob_loss, "effective_samples": rob_samples,
        "state_finite": rob_finite, "rollbacks": rollbacks,
        "robust_records": n_robust_records,
        "max_anomaly_score": float(max_score),
        "anomalous_is_corrupt_learner": bool(anomalous_is_bad),
    })

    # --- robust hooks off == bitwise identity -----------------------------
    short = max(steps // 2, 8)
    tr_a = _make_trainer(short)
    tr_a.run(log=None)
    tr_b = _make_trainer(short, robust=INERT)
    tr_b.run(log=None)
    bitwise_off = bool(
        np.array_equal(np.asarray(tr_a.state.global_params),
                       np.asarray(tr_b.state.global_params))
        and np.array_equal(np.asarray(tr_a.state.learners),
                           np.asarray(tr_b.state.learners))
        and np.array_equal(np.asarray(tr_a.state.momentum),
                           np.asarray(tr_b.state.momentum))
    )
    tr_a.close()
    tr_b.close()
    rows.append({
        "kind": "robust_measured", "cell": "robust_off",
        "bitwise_identical": bitwise_off,
    })

    for r in rows:
        print("robust," + ",".join(
            f"{k}={v}" for k, v in r.items() if k != "kind"
        ))

    # --- acceptance -------------------------------------------------------
    bar = base_loss_at(rob_samples)
    gap = rob_loss / bar
    # the corrupted plain mean must be demonstrably WORSE than the robust
    # run — otherwise the injected corruption is too weak for the 5%
    # bound to mean anything
    mean_degrades = mean_gap > 1.5 * max(gap, 1.0)
    accept = {
        "kind": "robust_accept",
        "loss_fault_free": bar,
        "loss_fault_free_full": base_loss,
        "loss_robust": rob_loss,
        "loss_mean_corrupt": mean_loss,
        "loss_vs_fault_free": gap,
        "within_5pct": bool(gap <= 1.05),
        "mean_degrades": bool(mean_degrades),
        "samples_vs_fault_free": rob_samples / max(base_samples, 1),
        "rollbacks": rollbacks,
        "state_finite": bool(rob_finite),
        "bitwise_off": bitwise_off,
        "anomalous_is_corrupt_learner": bool(anomalous_is_bad),
        "ok": bool(
            gap <= 1.05 and mean_degrades and rollbacks == 0
            and rob_finite and bitwise_off and anomalous_is_bad
        ),
    }
    rows.append(accept)
    print(f"robust_accept,loss_vs_fault_free,{gap:.3f},within_5pct,"
          f"{accept['within_5pct']},mean_degrades,{mean_degrades},"
          f"rollbacks,{rollbacks},bitwise_off,{bitwise_off},"
          f"anomalous_is_corrupt_learner,{anomalous_is_bad}")
    return rows


def main(quick: bool = False, json_path: str | None = None) -> list[dict]:
    rows = measured(quick)
    if json_path:
        from benchmarks.common import write_rows

        write_rows(json_path, rows, suite="robust")
        print(f"wrote {len(rows)} rows to {json_path}")
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true", help="few steps (CI)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also dump all rows as JSON")
    args = ap.parse_args()
    main(quick=args.smoke, json_path=args.json)
