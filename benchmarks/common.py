"""Shared helpers for the benchmark harness."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import MAvgConfig
from repro.core.meta import init_state, make_meta_step
from repro.data import classif_batch_fn, classif_eval_set
from repro.models.simple import mlp_accuracy, mlp_init, mlp_loss
from repro.pack import unpack_params

D_IN, CLASSES, HIDDEN = 32, 10, 64


def run_mlp(algorithm: str, *, P: int, K: int, mu: float, lr: float = 0.2,
            steps: int = 60, batch: int = 16, seed: int = 0,
            local_momentum: float = 0.0, staleness: int = 1,
            elastic_alpha: float = 0.05, comm=None, topology=None):
    """Train the teacher-classification MLP; returns (losses, val_acc).

    ``comm``: optional CommConfig selecting the meta-communication
    compression scheme (default dense / exact averaging). ``topology``:
    optional TopologyConfig selecting the meta-level mixing structure
    (default flat all-reduce).
    """
    extra = {} if comm is None else {"comm": comm}
    if topology is not None:
        extra["topology"] = topology
    cfg = MAvgConfig(
        algorithm=algorithm, num_learners=P, k_steps=K, learner_lr=lr,
        momentum=mu, local_momentum=local_momentum, staleness=staleness,
        elastic_alpha=elastic_alpha, **extra,
    )
    params = mlp_init(jax.random.PRNGKey(seed), D_IN, HIDDEN, CLASSES)
    state = init_state(params, cfg)
    step = jax.jit(make_meta_step(mlp_loss, cfg))
    bf = classif_batch_fn(D_IN, CLASSES, P, K, batch)
    losses = []
    for i in range(steps):
        b = bf(jax.random.fold_in(jax.random.PRNGKey(seed + 1), i), i)
        state, m = step(state, b)
        losses.append(float(m["loss"]))
    eval_set = classif_eval_set(D_IN, CLASSES)
    acc = float(mlp_accuracy(unpack_params(state), eval_set))
    return losses, acc


def samples_to_target(losses, target: float, P: int, K: int, batch: int):
    """First sample count at which the running-min loss crosses target.

    This is the paper's speed-up metric (Lemma 4): M-AVG reaches a target
    accuracy with fewer samples than K-AVG. Returns None if never reached.
    """
    best = float("inf")
    for i, l in enumerate(losses):
        best = min(best, l)
        if best <= target:
            return (i + 1) * P * K * batch
    return None


def write_rows(path: str, rows: list, suite: str) -> str:
    """The one ``--json PATH`` writer every bench shares.

    Emits the ``repro.obs`` run-log envelope (DESIGN.md §11): a
    ``{"kind": "manifest", ...}`` first line carrying the suite name plus
    the jax/device environment, then one ``{"kind": "row", ...}`` line
    per result row — the same JSONL stream format Trainer run logs use,
    so one reader (and ``tools/check_telemetry.py``) covers both.
    """
    from repro.obs import JsonlSink, run_manifest

    sink = JsonlSink(path)
    sink.open_run(run_manifest(suite=suite))
    for r in rows:
        rec = dict(r)
        # benches use "kind" for their own row taxonomy (parity /
        # hbm_passes / ...); the envelope tag must stay "row", so the
        # bench taxonomy moves to "row_kind"
        if "kind" in rec:
            rec["row_kind"] = rec.pop("kind")
        sink.append({"kind": "row", **rec})
    sink.close()
    return path

