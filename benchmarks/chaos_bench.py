"""Chaos/recovery benchmark (repro.chaos + core.supervisor, DESIGN.md §13).

The acceptance experiment the fault-injection subsystem exists for: under
the STANDARD fault schedule (a learner crash window, a NaN batch burst,
payload scale+bitflip corruption, a straggle spike and a torn checkpoint
write — repro.chaos.standard_chaos), a supervised run with the in-step
finite guard and the verified checkpoint chain must converge within 5%
of the fault-free final loss at equal effective samples, with zero
non-finite values ever entering ``MetaState``.

Arms:

  fault_free        the same config, no chaos, no guard — the loss bar
  chaos_supervised  standard chaos + finite_guard + Supervisor rollback/
                    retry over the verified checkpoint chain
  injectors_off     chaos installed but EMPTY (corruptor idle, guard on)
                    vs vanilla — final state must be BITWISE identical
  kill_mid_save     a torn write at the head of the checkpoint chain —
                    ``latest_verified_checkpoint`` must fall back to the
                    previous snapshot bit-exactly

Prints ``chaos,...`` CSV lines; ``--json PATH`` dumps every row.
``--smoke`` shrinks steps; tests/test_chaos.py (CH6) holds the
acceptance row at that size.
"""
from __future__ import annotations

import argparse
import os
import sys
import tempfile

if __package__ in (None, ""):  # `python benchmarks/chaos_bench.py --smoke`
    _root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(_root, "src"))
    sys.path.insert(0, _root)

import numpy as np

import jax

from benchmarks.common import CLASSES, D_IN, HIDDEN
from repro.chaos import ChaosConfig, standard_chaos
from repro.checkpoint import (
    latest_verified_checkpoint,
    load_state,
    save_state,
    verify_checkpoint,
)
from repro.configs.base import (
    AsyncConfig,
    MAvgConfig,
    ObsConfig,
    TopologyConfig,
    TrainConfig,
)
from repro.core import RecoveryPolicy, Supervisor, Trainer
from repro.data import classif_batch_fn
from repro.models.simple import mlp_init, mlp_loss

P, K, MU, LR, BATCH = 4, 4, 0.7, 0.2, 16
TAU = 2


def _make_trainer(steps, *, chaos=None, guard=False, salt=0, lr_scale=1.0,
                  ckpt_dir=None, health=False, momentum_scale=1.0):
    mcfg = MAvgConfig(
        algorithm="mavg", num_learners=P, k_steps=K,
        learner_lr=LR * lr_scale, momentum=MU * momentum_scale,
        finite_guard=guard,
        topology=TopologyConfig(kind="async",
                                server=AsyncConfig(staleness=TAU)),
    )
    tcfg = TrainConfig(
        model=None, mavg=mcfg, batch_per_learner=BATCH, meta_steps=steps,
        seed=0, log_every=2,
        checkpoint_dir=ckpt_dir,
        checkpoint_every=2 if ckpt_dir else 0,
        checkpoint_keep=4 if ckpt_dir else 0,
        chaos=chaos, data_salt=salt,
        obs=ObsConfig(sink="none", health=health),
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
    # smoke needs enough post-fault room for a full rollback replay to
    # re-converge: the supervisor resumes from the newest snapshot
    # STRICTLY before the fault, so one recovery re-pays a few steps
    steps = 24 if quick else 40
    rows: list[dict] = []

    # --- fault-free bar ---------------------------------------------------
    tr = _make_trainer(steps)
    base_hist = tr.run(log=None)
    base_loss = _final_loss(base_hist)
    base_samples = base_hist[-1]["samples"]
    tr.close()
    rows.append({
        "kind": "chaos_measured", "cell": "fault_free",
        "final_loss": base_loss, "effective_samples": base_samples,
        "state_finite": _state_finite(tr.state),
    })

    def base_loss_at(samples):
        """Fault-free loss at ``samples`` effective samples — the equal-
        effective-samples bar (crash windows and quarantine probation
        cost the supervised run samples it never gets back; the fair
        comparison charges the fault-free arm the same budget)."""
        upto = (
            [r for r in base_hist if r["samples"] <= samples]
            or base_hist[:1]
        )
        return _final_loss(upto)

    # --- supervised run under the standard fault schedule -----------------
    chaos = standard_chaos(P, steps, seed=0)
    tmp = tempfile.mkdtemp(prefix="chaos_bench_")
    ckpt_dir = os.path.join(tmp, "ckpt")

    def make_trainer(plan):
        return _make_trainer(
            steps, chaos=chaos, guard=True, salt=plan.data_salt,
            lr_scale=plan.lr_scale, momentum_scale=plan.momentum_scale,
            ckpt_dir=ckpt_dir, health=True,
        )

    sup = Supervisor(
        make_trainer, target_steps=steps, checkpoint_dir=ckpt_dir,
        policy=RecoveryPolicy(max_retries=3,
                              quarantine_steps=max(steps // 8, 2)),
    )
    tr, hist = sup.run(log=None)
    sup_loss = _final_loss(tr.history)
    sup_samples = tr.history[-1]["samples"]
    retries = max(
        (r["attempt"] for r in sup.records if r.get("kind") == "recovery"),
        default=0,
    )
    sup_finite = _state_finite(tr.state)
    # every retained snapshot of the chain must verify finite too — the
    # "zero non-finite values ever entering MetaState" claim is checked
    # at each point the state was durably observed
    chain_ok = True
    for f in sorted(os.listdir(ckpt_dir)):
        if f.endswith(".npz"):
            try:
                verify_checkpoint(os.path.join(ckpt_dir, f))
            except Exception:
                chain_ok = False
    tr.close()
    rows.append({
        "kind": "chaos_measured", "cell": "chaos_supervised",
        "final_loss": sup_loss, "effective_samples": sup_samples,
        "state_finite": sup_finite, "chain_verified": chain_ok,
        "retries_used": retries,
        "faults_injected": len(chaos.faults),
    })

    # --- injectors off == bitwise identity --------------------------------
    tr_a = _make_trainer(max(steps // 4, 8))
    tr_a.run(log=None)
    tr_b = _make_trainer(max(steps // 4, 8),
                         chaos=ChaosConfig(seed=0, horizon=steps, faults=()),
                         guard=True)
    tr_b.run(log=None)
    bitwise_off = bool(
        np.array_equal(np.asarray(tr_a.state.global_params),
                       np.asarray(tr_b.state.global_params))
        and np.array_equal(np.asarray(tr_a.state.learners),
                           np.asarray(tr_b.state.learners))
        and np.array_equal(np.asarray(tr_a.state.momentum),
                           np.asarray(tr_b.state.momentum))
    )
    rows.append({
        "kind": "chaos_measured", "cell": "injectors_off",
        "bitwise_identical": bitwise_off,
    })

    # --- kill mid-save: the chain falls back bit-exactly -------------------
    kdir = os.path.join(tmp, "killsave")
    good = save_state(kdir, tr_a.state, 8)
    save_state(kdir, tr_a.state, 9, fault="torn")
    fallback = latest_verified_checkpoint(kdir)
    resume_ok = fallback == good
    if resume_ok:
        restored = load_state(good, tr_a.state)
        resume_ok = bool(np.array_equal(
            np.asarray(restored.global_params),
            np.asarray(tr_a.state.global_params),
        ))
    rows.append({
        "kind": "chaos_measured", "cell": "kill_mid_save",
        "resume_verified": bool(resume_ok),
    })

    for r in rows:
        print("chaos," + ",".join(
            f"{k}={v}" for k, v in r.items() if k != "kind"
        ))

    # --- acceptance -------------------------------------------------------
    bar = base_loss_at(sup_samples)
    gap = sup_loss / bar
    accept = {
        "kind": "chaos_accept",
        "loss_fault_free": bar,
        "loss_fault_free_full": base_loss,
        "loss_supervised": sup_loss,
        "loss_vs_fault_free": gap,
        "within_5pct": bool(gap <= 1.05),
        "samples_vs_fault_free": sup_samples / max(base_samples, 1),
        "state_finite": bool(sup_finite and chain_ok),
        "bitwise_off": bitwise_off,
        "resume_verified": bool(resume_ok),
        "retries_used": retries,
        "ok": bool(
            gap <= 1.05 and sup_finite and chain_ok and bitwise_off
            and resume_ok
        ),
    }
    rows.append(accept)
    print(f"chaos_accept,loss_vs_fault_free,{gap:.3f},within_5pct,"
          f"{accept['within_5pct']},state_finite,{accept['state_finite']},"
          f"bitwise_off,{bitwise_off},resume_verified,{resume_ok},"
          f"retries,{retries}")
    return rows


def main(quick: bool = False, json_path: str | None = None) -> list[dict]:
    rows = measured(quick)
    if json_path:
        from benchmarks.common import write_rows

        write_rows(json_path, rows, suite="chaos")
        print(f"wrote {len(rows)} rows to {json_path}")
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true", help="few steps (CI)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also dump all rows as JSON")
    args = ap.parse_args()
    main(quick=args.smoke, json_path=args.json)
