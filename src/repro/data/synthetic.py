"""Deterministic synthetic data pipelines.

Two requirements drive the design:
1. Convergence experiments (the paper's claims) need *learnable* data so
   loss curves mean something: we use a fixed random bigram teacher for LM
   data and a fixed random teacher network for classification data.
2. Learners must see disjoint i.i.d. streams (Assumption 1's i.i.d. xi^j):
   every (learner, meta_step, local_step) triple gets an independent fold
   of the seed, so runs are reproducible across algorithms and P.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig

# ---------------------------------------------------------------------------
# bigram-teacher LM stream
# ---------------------------------------------------------------------------


def bigram_table(seed: int, vocab: int, concentration: float = 0.3):
    """Row-stochastic transition matrix with low entropy (learnable)."""
    key = jax.random.PRNGKey(seed)
    logits = jax.random.normal(key, (vocab, vocab)) / concentration
    return jax.nn.softmax(logits, axis=-1)


# largest vocabulary the dense (V, V) bigram table is built for (64 MiB in
# f32); a full-width vocabulary (50k tokens: a 10 GB table) gets the
# sparse teacher below instead
DENSE_TEACHER_MAX_VOCAB = 4096
SPARSE_TEACHER_SUPPORT = 64


def sparse_bigram_teacher(seed: int, vocab: int,
                          support: int = SPARSE_TEACHER_SUPPORT,
                          concentration: float = 0.3):
    """Bigram teacher for a large vocabulary: each token moves to one of
    ``support`` random successors, drawn with low-entropy logits.
    Returns (successors (V, support) int32, logits (V, support) f32) —
    O(V * support) memory instead of the dense table's O(V^2)."""
    k_s, k_l = jax.random.split(jax.random.PRNGKey(seed))
    succ = jax.random.randint(k_s, (vocab, support), 0, vocab, jnp.int32)
    logits = jax.random.normal(k_l, (vocab, support)) / concentration
    return succ, logits


def lm_teacher(seed: int, vocab: int):
    """The dense bigram table up to ``DENSE_TEACHER_MAX_VOCAB`` tokens,
    the sparse teacher above it; either one feeds ``sample_lm``."""
    if vocab <= DENSE_TEACHER_MAX_VOCAB:
        return bigram_table(seed, vocab)
    return sparse_bigram_teacher(seed, vocab)


@partial(jax.jit, static_argnums=(2, 3))
def sample_lm(key, table, batch: int, seq_len: int):
    """Sample (batch, seq_len) token sequences from a bigram teacher: the
    dense (V, V) table or a ``sparse_bigram_teacher`` pair."""
    k0, k1 = jax.random.split(key)
    sparse = isinstance(table, tuple)
    vocab = table[0].shape[0] if sparse else table.shape[0]
    first = jax.random.randint(k0, (batch,), 0, vocab)

    def step(tok, k):
        if sparse:
            succ, logits = table
            j = jax.random.categorical(k, logits[tok])
            nxt = jnp.take_along_axis(succ[tok], j[:, None], axis=1)[:, 0]
        else:
            nxt = jax.random.categorical(k, jnp.log(table[tok] + 1e-9))
        return nxt, nxt

    ks = jax.random.split(k1, seq_len - 1)
    _, rest = lax.scan(step, first, ks)
    toks = jnp.concatenate([first[None], rest], axis=0).T  # (B, S)
    return toks.astype(jnp.int32)


def lm_batch_fn(model_cfg: ModelConfig, num_learners: int, k_steps: int,
                batch: int, seq_len: int, table_seed: int = 1234):
    """Returns ``batch_fn(rng, step)`` producing (L, K, B, S) token batches."""
    table = lm_teacher(table_seed, model_cfg.vocab_size)

    def batch_fn(rng, step):
        ks = jax.random.split(rng, num_learners * k_steps)
        toks = jnp.stack(
            [sample_lm(k, table, batch, seq_len) for k in ks]
        ).reshape(num_learners, k_steps, batch, seq_len)
        return {"tokens": toks, "labels": toks}

    return batch_fn


# ---------------------------------------------------------------------------
# teacher-network classification stream (the paper's CIFAR-10 stand-in)
# ---------------------------------------------------------------------------


def make_teacher(seed: int, d_in: int, classes: int, hidden: int = 64):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    return {
        "w1": jax.random.normal(k1, (d_in, hidden)) / jnp.sqrt(d_in),
        "w2": jax.random.normal(k2, (hidden, classes)) / jnp.sqrt(hidden),
    }


@jax.jit
def _teacher_labels(teacher, x):
    h = jnp.tanh(x @ teacher["w1"])
    return jnp.argmax(h @ teacher["w2"], axis=-1).astype(jnp.int32)


def classif_batch_fn(d_in: int, classes: int, num_learners: int, k_steps: int,
                     batch: int, teacher_seed: int = 7, noise: float = 0.0):
    teacher = make_teacher(teacher_seed, d_in, classes)

    @partial(jax.jit, static_argnums=())
    def gen(rng):
        L, K, B = num_learners, k_steps, batch
        kx, kn = jax.random.split(rng)
        x = jax.random.normal(kx, (L, K, B, d_in))
        y = _teacher_labels(teacher, x.reshape(-1, d_in)).reshape(L, K, B)
        if noise:
            x = x + noise * jax.random.normal(kn, x.shape)
        return {"x": x, "y": y}

    def batch_fn(rng, step):
        return gen(rng)

    return batch_fn


# ---------------------------------------------------------------------------
# fixed evaluation sets (validation accuracy, as in the paper's Table I)
# ---------------------------------------------------------------------------


def classif_eval_set(d_in: int, classes: int, n: int = 2048, teacher_seed: int = 7,
                     seed: int = 99):
    teacher = make_teacher(teacher_seed, d_in, classes)
    x = jax.random.normal(jax.random.PRNGKey(seed), (n, d_in))
    y = _teacher_labels(teacher, x)
    return {"x": x, "y": y}


def lm_eval_set(model_cfg: ModelConfig, n: int = 64, seq_len: int = 64,
                table_seed: int = 1234, seed: int = 98):
    table = lm_teacher(table_seed, model_cfg.vocab_size)
    toks = sample_lm(jax.random.PRNGKey(seed), table, n, seq_len)
    return {"tokens": toks, "labels": toks}
