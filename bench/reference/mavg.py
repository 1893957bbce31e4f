"""Plain M-AVG (block momentum over K local SGD steps) on the reference
xLSTM, from the seed, with the readings that decide ``correct``.

Meta step n, with learner copies stored in ``storage`` (the precision the
configuration states for them) and everything else in float32:

    for each learner j:  w_j = cast(w~, storage)
        for each of K local steps:  w_j = cast(w_j - lr_n * grad(w_j), storage)
    a = mean_j w_j;  v = mu v + eta (a - w~);  w~ = w~ + v

The gradient is taken at the float32 value of the stored copy, at
``precision="highest"``. The weights come from ``init_key`` and the
batches from ``data_key``, as ``weights_and_data_keys`` derives them from
the run's seed.

``fault`` plants a fault the correctness check must catch, in the
reference put in the program's place: ``half_batch`` (each local step
sees only the first half of its batch, the mean taken over it).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import data as refdata
from bench.reference import xlstm


def weights_and_data_keys(weight_seed: int, data_salt: int):
    """(init key, data key): the seed's key split in two, the data half
    folded with the salt when it is non-zero."""
    data_key, init_key = jax.random.split(jax.random.PRNGKey(weight_seed))
    if data_salt:
        data_key = jax.random.fold_in(data_key, data_salt)
    return init_key, data_key


def flat_paths(tree) -> list[str]:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return ["/".join(str(getattr(k, "key", k)) for k in path)
            for path, _ in flat]


@jax.jit
def leaf_norms(tree):
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for x in jax.tree.leaves(tree)]


def _norm_dict(tree) -> dict[str, float]:
    return dict(zip(flat_paths(tree), (float(n) for n in leaf_norms(tree))))


@partial(jax.jit, static_argnames=("cfg", "storage"), donate_argnums=(0,))
def _local_step(w, tokens, lr, *, cfg, storage):
    cfg = dict(cfg)
    w32 = jax.tree.map(lambda a: a.astype(jnp.float32), w)
    value, g = jax.value_and_grad(xlstm.loss)(w32, tokens, cfg)
    w = jax.tree.map(lambda a, b: (a - lr * b).astype(storage), w32, g)
    return value, w, [jnp.sqrt(jnp.sum(jnp.square(x))) for x in jax.tree.leaves(g)]


@partial(jax.jit, static_argnames=("storage",))
def _cast(tree, *, storage):
    return jax.tree.map(lambda a: a.astype(storage), tree)


@jax.jit
def _as_f32(w):
    return jax.tree.map(lambda a: a.astype(jnp.float32), w)


@partial(jax.jit, donate_argnums=(0,))
def _accumulate(total, w):
    return jax.tree.map(lambda t, a: t + a.astype(jnp.float32), total, w)


@partial(jax.jit, static_argnames=("storage",))
def _movement(total, w, n, *, storage):
    """The learners' mean movement from the copy they started at."""
    return jax.tree.map(lambda t, a: t / n - a.astype(storage).astype(
        jnp.float32), total, w)


@partial(jax.jit, donate_argnums=(0, 1))
def _meta_update(w, v, total, n, mu, eta):
    v = jax.tree.map(lambda vi, ti, wi: mu * vi + eta * (ti / n - wi),
                     v, total, w)
    w = jax.tree.map(jnp.add, w, v)
    return w, v


def run(cfg: dict, job: dict, weight_seed: int, data_salt: int, steps: int,
        storage: str = "bfloat16", fault: str | None = None) -> dict:
    """Run ``steps`` meta steps of the reference and return its readings:

    loss            per meta step, the mean over learners and local steps
    first_grad      per leaf, the norm of v after the first meta step (the
                    block momentum's first gradient)
    first_move      per leaf, the norm of the learners' mean movement in
                    the first meta step, mean_j w_j - cast(w~)
    change          per leaf, the norm of w~ after ``steps`` minus w~ at 0
    local_grad      per leaf, the norm of learner 0's first local gradient
    """
    if fault not in (None, "half_batch"):
        raise ValueError(f"unknown fault {fault!r}")
    ckey = tuple(sorted(cfg.items()))
    L, K, B, S = job["learners"], job["k"], job["batch"], job["seq"]
    init_key, data_key = weights_and_data_keys(weight_seed, data_salt)
    table = refdata.teacher(cfg["vocab_size"])
    init = jax.jit(partial(xlstm.init_weights, cfg=cfg))
    w = init(init_key)
    v = jax.tree.map(jnp.zeros_like, w)
    out = {"loss": []}
    for n in range(steps):
        lr = refdata.learning_rate(n, job["lr"], job["warmup"],
                                   job["schedule_steps"])
        toks = refdata.batches(data_key, n, table, L, K, B, S)
        if fault == "half_batch":
            toks = toks[:, :, :B // 2]
        total, losses = None, []
        for j in range(L):
            wj = _cast(w, storage=storage)
            for k in range(K):
                value, wj, gn = _local_step(wj, toks[j, k], lr, cfg=ckey,
                                            storage=storage)
                if n == 0 and j == 0 and k == 0:
                    out["local_grad"] = dict(zip(flat_paths(w),
                                                 (float(x) for x in gn)))
                losses.append(value)
            total = _as_f32(wj) if total is None else _accumulate(total, wj)
            del wj
        if n == 0:
            out["first_move"] = _norm_dict(_movement(total, w, jnp.float32(L),
                                                     storage=storage))
        w, v = _meta_update(w, v, total, jnp.float32(L),
                            jnp.float32(job["momentum"]),
                            jnp.float32(job.get("meta_lr", 1.0)))
        del total
        out["loss"].append(float(np.mean([float(x) for x in losses])))
        if n == 0:
            out["first_grad"] = _norm_dict(v)
    del v
    out["change"] = _norm_dict(jax.tree.map(jnp.subtract, w, init(init_key)))
    return out
