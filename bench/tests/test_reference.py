"""The plain reference against the program's model and meta step, at the
reduced xlstm configuration on the CPU, all in float32: the weights and
batches are the same bits, and the loss, the gradient and one M-AVG meta
step agree to float32 rounding. The on-chip comparison then starts from a
reference known to compute what the program computes."""
import statistics
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import program
from bench.reference import data as refdata
from bench.reference import mavg, xlstm as refx

MODEL = {"num_layers": 2, "d_model": 256, "num_heads": 4, "vocab_size": 512,
         "ssm_expand": 2, "ssm_conv": 4, "slstm_every": 2, "norm_eps": 1e-5}
SEED = 2 ** 31 + 4321
L, K, B, S = 2, 2, 2, 128


@pytest.fixture(scope="module")
def prog():
    from repro.configs.base import get_config
    from repro.models import api

    cfg = replace(get_config("xlstm-350m").reduced(), dtype="float32")
    assert {k: getattr(cfg, k) for k in MODEL} == MODEL
    ws, salt = program.seeds(SEED)
    data_key, init_key = jax.random.split(jax.random.PRNGKey(ws))
    return {"cfg": cfg, "api": api, "params": api.init_params(init_key, cfg),
            "data_key": jax.random.fold_in(data_key, salt), "ws": ws,
            "salt": salt}


def _paths(tree):
    return mavg.flat_paths(tree)


def test_same_weights_and_batches(prog):
    from repro.data import lm_batch_fn

    init_key, data_key = mavg.weights_and_data_keys(prog["ws"], prog["salt"])
    ref = refx.init_weights(init_key, MODEL)
    assert _paths(ref) == _paths(prog["params"])
    for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(prog["params"])):
        assert a.shape == b.shape and bool(jnp.all(a == b))
    ours = refdata.batches(data_key, 3, refdata.teacher(512), L, K, B, S)
    theirs = lm_batch_fn(prog["cfg"], L, K, B, S)(
        jax.random.fold_in(prog["data_key"], 3), 3)["tokens"]
    assert bool(jnp.all(ours == theirs))


def test_loss_and_gradient_agree(prog):
    toks = refdata.batches(prog["data_key"], 0, refdata.teacher(512),
                           L, K, B, S)[0, 0]
    batch = {"tokens": toks, "labels": toks}
    with jax.default_matmul_precision("highest"):
        lp, gp = jax.value_and_grad(
            lambda p: prog["api"].loss_fn(p, prog["cfg"], batch)[0])(
                prog["params"])
    lr, gr = jax.value_and_grad(refx.loss)(prog["params"], toks, MODEL)
    # float32 rounding of a mean over B x (S - 1) log-probabilities
    assert abs(float(lp) - float(lr)) < 1e-5
    norms = [float(jnp.linalg.norm(g)) for g in jax.tree.leaves(gr)]
    med = statistics.median(norms)
    for a, b, n in zip(jax.tree.leaves(gp), jax.tree.leaves(gr), norms):
        # relative to the leaf, or to the median leaf where the leaf's
        # gradient is nought to rounding (the input-gate biases)
        assert float(jnp.linalg.norm(a - b)) <= 1e-4 * max(n, med)


def test_one_meta_step_agrees(prog):
    from repro.configs.base import MAvgConfig
    from repro.core.meta import init_state, make_meta_step
    from repro.pack import unpack_params

    mcfg = MAvgConfig(num_learners=L, k_steps=K, learner_lr=0.1,
                      momentum=0.7, compute_dtype="float32",
                      use_pallas=False, donate=False)
    loss_fn = lambda p, b: prog["api"].loss_fn(p, prog["cfg"], b)
    state = init_state(prog["params"], mcfg)
    toks = refdata.batches(prog["data_key"], 0, refdata.teacher(512),
                           L, K, B, S)
    lr = refdata.learning_rate(0, 0.3, 5, 100)
    with jax.default_matmul_precision("highest"):
        state, metrics = jax.jit(make_meta_step(loss_fn, mcfg))(
            state, {"tokens": toks, "labels": toks}, lr=lr)
    job = {"learners": L, "k": K, "batch": B, "seq": S, "lr": 0.3,
           "warmup": 5, "schedule_steps": 100, "momentum": 0.7}
    ref = mavg.run(MODEL, job, prog["ws"], prog["salt"], 1,
                   storage="float32")
    assert abs(float(metrics["loss"]) - ref["loss"][0]) < 1e-5
    v = unpack_params(replace(state, global_params=state.momentum))
    w = unpack_params(state)
    med = statistics.median(ref["first_grad"].values())
    for path, a, b in zip(_paths(w), jax.tree.leaves(v),
                          jax.tree.leaves(w)):
        change = float(np.linalg.norm(np.asarray(b) - np.asarray(
            [x for p, x in zip(_paths(w), jax.tree.leaves(prog["params"]))
             if p == path][0])))
        scale = max(ref["first_grad"][path], med)
        assert abs(float(jnp.linalg.norm(a)) - ref["first_grad"][path]) \
            <= 1e-3 * scale, path
        assert abs(change - ref["change"][path]) <= 1e-3 * scale, path
