"""Async bounded-staleness server benchmark (repro.topology.async_server).

The experiment the synchronizer refactor exists for: under a skewed
per-learner step-time profile, the synchronous barrier pays the
straggler's block time every round (idle = 1 - mean/max of the profile),
while the async server keeps every learner busy and applies pushes with
staleness-decayed weight. Three arms at EQUAL EFFECTIVE SAMPLES
(completed K-step blocks x K x batch):

  sync     flat M-AVG — the barrier; wall-clock charged max(profile)
           ticks per round
  async    bounded-staleness server on the same skewed profile — one
           tick per dispatch, pushes when ready
  elastic  masking the straggler out instead of waiting for it (the §8
           alternative: drop vs lag) — runs at the fast learners' pace
           but throws the straggler's samples away

Acceptance (ROADMAP): at 4x skew the async arm lands within 5% of the
synchronous final loss at equal effective samples, while the barrier
would idle >= 40% of wall-clock; applied staleness stays <= tau on every
tick. A modeled layer prices the per-tick wire under the same profile
(roofline.topology_wire_bytes "async" arm).

Prints ``async,...`` CSV lines; ``--json PATH`` dumps every row.
``--smoke`` shrinks steps; tests/test_async.py (A8) holds the
acceptance rows at that size.
"""
from __future__ import annotations

import argparse
import math
import os
import sys

if __package__ in (None, ""):  # `python benchmarks/async_bench.py --smoke`
    _root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(_root, "src"))
    sys.path.insert(0, _root)

import jax

from benchmarks.common import CLASSES, D_IN, HIDDEN
from repro.configs.base import (
    AsyncConfig,
    CommConfig,
    ElasticConfig,
    MAvgConfig,
    TopologyConfig,
    get_config,
)
from repro.core.meta import init_state, make_meta_step
from repro.data import classif_batch_fn, classif_eval_set
from repro.models.simple import mlp_accuracy, mlp_init, mlp_loss
from repro.pack import unpack_params
from repro.roofline import DCN_LINK_BW, ICI_LINK_BW, topology_wire_bytes
from repro.topology import make_topology

P, K, MU, LR, BATCH = 8, 4, 0.7, 0.2, 16

# 4x skew: half the learners at full speed, a 2x and a 4x straggler pair
PROFILE = (1, 1, 1, 1, 2, 2, 4, 4)
TAU = max(PROFILE) - 1


def _run(topology, ticks, *, seed=0):
    """Train the teacher-classification MLP for ``ticks`` meta steps,
    returning (losses, val_acc, per-step metrics, topology instance)."""
    cfg = MAvgConfig(algorithm="mavg", num_learners=P, k_steps=K,
                     learner_lr=LR, momentum=MU, topology=topology)
    topo = make_topology(cfg)
    params = mlp_init(jax.random.PRNGKey(seed), D_IN, HIDDEN, CLASSES)
    state = init_state(params, cfg, topology=topo)
    step = jax.jit(make_meta_step(mlp_loss, cfg, topology=topo))
    bf = classif_batch_fn(D_IN, CLASSES, P, K, BATCH)
    losses, metrics = [], []
    for i in range(ticks):
        b = bf(jax.random.fold_in(jax.random.PRNGKey(seed + 1), i), i)
        state, m = step(state, b)
        losses.append(float(m["loss"]))
        metrics.append({k: float(v) for k, v in m.items()})
    acc = float(mlp_accuracy(unpack_params(state),
                             classif_eval_set(D_IN, CLASSES)))
    return losses, acc, metrics, topo


def _final(losses):
    tail = losses[-5:]
    return sum(tail) / len(tail)


def measured(quick: bool) -> list[dict]:
    sync_rounds = 15 if quick else 60
    prof = PROFILE
    target_blocks = sync_rounds * P  # the sync arm's completed blocks
    samples_per_block = K * BATCH

    # --- sync: the barrier pays the straggler every round -----------------
    losses, acc, _, _ = _run(TopologyConfig(kind="flat"), sync_rounds)
    sync_wall = sync_rounds * max(prof)
    sync_idle = 1.0 - (sum(prof) / len(prof)) / max(prof)
    rows = [{
        "kind": "async_measured", "cell": "sync_barrier",
        "final_loss": _final(losses), "val_acc": acc,
        "effective_samples": target_blocks * samples_per_block,
        "wall_clock_ticks": sync_wall, "idle_frac": sync_idle,
        "staleness_max": 0.0,
    }]

    # --- async: run until the same number of blocks completed -------------
    atopo = TopologyConfig(kind="async",
                           server=AsyncConfig(staleness=TAU, step_time=prof))
    probe = make_topology(MAvgConfig(num_learners=P, k_steps=K,
                                     topology=atopo))
    ticks = 1
    while probe.work_completed(ticks - 1) < target_blocks:
        ticks += 1
    losses, acc, metrics, topo = _run(atopo, ticks)
    stale_worst = max(m["staleness_max"] for m in metrics)
    rows.append({
        "kind": "async_measured", "cell": f"async_skew{max(prof)}x",
        "final_loss": _final(losses), "val_acc": acc,
        "effective_samples":
            topo.work_completed(ticks - 1) * samples_per_block,
        "wall_clock_ticks": ticks, "idle_frac": 0.0,
        "staleness_max": stale_worst, "staleness_bound": TAU,
    })

    # --- elastic masking: drop the stragglers instead of waiting ----------
    # (drop vs lag, §8 vs §12): 25% absent ~= masking out the 4x pair;
    # present learners run at full speed, the absentees' samples are lost
    etopo = TopologyConfig(kind="hierarchical", groups=2, outer_every=1,
                           elastic=ElasticConfig(period=8, drop_frac=0.25))
    presence = 0.75
    eticks = math.ceil(sync_rounds / presence)
    losses, acc, _, _ = _run(etopo, eticks)
    rows.append({
        "kind": "async_measured", "cell": "elastic_mask25",
        "final_loss": _final(losses), "val_acc": acc,
        "effective_samples":
            int(eticks * P * presence) * samples_per_block,
        "wall_clock_ticks": eticks, "idle_frac": 0.0,
        "staleness_max": 0.0,
    })

    for r in rows:
        print(f"async,{r['cell']},final_loss,{r['final_loss']:.4f},"
              f"wall,{r['wall_clock_ticks']},idle,{r['idle_frac']:.2f},"
              f"stale_max,{r['staleness_max']:.0f}")

    # --- acceptance -------------------------------------------------------
    sync_row = rows[0]
    async_row = rows[1]
    gap = async_row["final_loss"] / sync_row["final_loss"]
    accept = {
        "kind": "async_accept",
        "loss_vs_sync_at_equal_samples": gap,
        "within_5pct": bool(gap <= 1.05),
        "sync_idle_frac": sync_idle,
        "sync_idles_40pct": bool(sync_idle >= 0.40),
        "staleness_max": stale_worst,
        "staleness_bound": TAU,
        "staleness_bounded": bool(stale_worst <= TAU),
        "wall_clock_speedup": sync_wall / async_row["wall_clock_ticks"],
    }
    rows.append(accept)
    print(f"async_accept,loss_vs_sync,{gap:.3f},within_5pct,"
          f"{accept['within_5pct']},sync_idle,{sync_idle:.2f},"
          f"speedup,{accept['wall_clock_speedup']:.2f}x")
    return rows


def modeled(arch: str = "qwen3-1.7b") -> list[dict]:
    n = get_config(arch).param_count()
    cells = (
        ("flat_dense", TopologyConfig()),
        ("async_uniform", TopologyConfig(
            kind="async", server=AsyncConfig())),
        ("async_skew4", TopologyConfig(
            kind="async", server=AsyncConfig(staleness=TAU,
                                             step_time=PROFILE))),
    )
    rows = []
    for name, topo in cells:
        edge = topology_wire_bytes(n, CommConfig(), topo, num_learners=P)
        wire_s = (edge["intra_bytes"] / ICI_LINK_BW
                  + edge["inter_bytes"] / DCN_LINK_BW)
        rows.append({"kind": "async_model", "cell": name, "arch": arch,
                     **edge, "wire_s": wire_s})
        print(f"async_model,{arch},{name},inter,"
              f"{edge['inter_bytes']:.3e},B,{wire_s:.4f},s")
    return rows


def main(quick: bool = False, json_path: str | None = None) -> list[dict]:
    rows = measured(quick) + modeled()
    if json_path:
        from benchmarks.common import write_rows

        write_rows(json_path, rows, suite="async")
        print(f"wrote {len(rows)} rows to {json_path}")
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true", help="few steps (CI)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also dump all rows as JSON")
    args = ap.parse_args()
    main(quick=args.smoke, json_path=args.json)
