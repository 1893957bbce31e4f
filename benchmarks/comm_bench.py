"""Meta-communication benchmark: bytes-on-wire and final loss per
compression scheme (the repro.comm subsystem).

Two layers of numbers:

1. *Measured* — the reducer's own ``comm_bytes`` metrics on the
   teacher-classification MLP, plus final training loss so compression
   quality is visible next to its savings.
2. *Modeled* — roofline.meta_wire_bytes on a full-scale config
   (qwen3-1.7b), showing what each scheme ships per meta step at
   production size and the resulting ICI link time.

Prints ``comm,...`` CSV lines. ``--smoke`` (or quick=True) shrinks steps
for CI.
"""
from __future__ import annotations

import argparse
import os
import sys

import jax

if __package__ in (None, ""):  # `python benchmarks/comm_bench.py --smoke`
    _root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(_root, "src"))
    sys.path.insert(0, _root)

from benchmarks.common import CLASSES, D_IN, HIDDEN, run_mlp
from repro.configs.base import CommConfig, MAvgConfig, get_config
from repro.core.meta import init_state, make_meta_step
from repro.data import classif_batch_fn
from repro.models.simple import mlp_init, mlp_loss
from repro.roofline import ICI_LINK_BW, meta_wire_bytes

SCHEMES = ("dense", "int8", "fp8", "topk", "int8_topk")


def _comm(scheme: str) -> CommConfig:
    return CommConfig(scheme=scheme, error_feedback=scheme != "dense")


def measured(quick: bool, *, P=4, K=4, mu=0.7, use_pallas=False):
    steps = 15 if quick else 60
    dense_loss = None
    for scheme in SCHEMES:
        comm = CommConfig(scheme=scheme, error_feedback=scheme != "dense",
                          use_pallas=use_pallas)
        losses, acc = run_mlp("mavg", P=P, K=K, mu=mu, steps=steps, comm=comm)

        # one jitted step on a fixed batch for the reducer's metrics
        cfg = MAvgConfig(algorithm="mavg", num_learners=P, k_steps=K,
                         learner_lr=0.2, momentum=mu, comm=comm)
        params = mlp_init(jax.random.PRNGKey(0), D_IN, HIDDEN, CLASSES)
        state = init_state(params, cfg)
        step = jax.jit(make_meta_step(mlp_loss, cfg))
        b = classif_batch_fn(D_IN, CLASSES, P, K, 16)(jax.random.PRNGKey(1), 0)
        _, m = step(state, b)

        wire = float(m["comm_bytes"])
        dense_b = float(m["comm_bytes_dense"])
        final = sum(losses[-5:]) / len(losses[-5:])
        if scheme == "dense":
            dense_loss = final
        print(f"comm,{scheme},bytes_wire,{wire:.0f},B")
        print(f"comm,{scheme},compression,{dense_b / wire:.2f},x")
        print(f"comm,{scheme},final_loss,{final:.4f},"
              f"{final / dense_loss:.3f}x_dense")
        print(f"comm,{scheme},val_acc,{acc:.3f},frac")


def modeled(arch: str = "qwen3-1.7b", P: int = 8):
    n = get_config(arch).param_count()
    for scheme in SCHEMES:
        dense, wire = meta_wire_bytes(n, _comm(scheme), num_learners=P)
        print(f"comm_model,{arch},{scheme},{wire:.3e},B,"
              f"{dense / wire:.2f},x,{wire / ICI_LINK_BW:.4f},s")


def main(quick: bool = False):
    measured(quick)
    modeled()


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="few steps (CI)")
    args = ap.parse_args()
    main(quick=args.smoke)
