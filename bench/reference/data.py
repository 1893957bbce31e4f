"""The training stream as the benchmark makes it, from the seed.

Token batches come from a bigram teacher: a dense (V, V) transition table
up to ``DENSE_MAX_VOCAB`` tokens, above that each token moves to one of
``SUPPORT`` random successors. The teacher is fixed (``TEACHER_SEED``);
the seed of a run picks the batches. Batch n of a run is drawn from
``fold_in(data_key, n)`` and holds L x K x B sequences of S tokens.

The learning rate of meta step n warms up linearly to ``lr`` over
``warmup`` steps, then decays on a cosine to ``final_frac * lr`` over the
remaining ``total - warmup`` steps.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

TEACHER_SEED = 1234
DENSE_MAX_VOCAB = 4096
SUPPORT = 64
CONCENTRATION = 0.3


def teacher(vocab: int):
    key = jax.random.PRNGKey(TEACHER_SEED)
    if vocab <= DENSE_MAX_VOCAB:
        logits = jax.random.normal(key, (vocab, vocab)) / CONCENTRATION
        return jax.nn.softmax(logits, axis=-1)
    k_s, k_l = jax.random.split(key)
    succ = jax.random.randint(k_s, (vocab, SUPPORT), 0, vocab, jnp.int32)
    logits = jax.random.normal(k_l, (vocab, SUPPORT)) / CONCENTRATION
    return succ, logits


@partial(jax.jit, static_argnums=(2, 3))
def sample(key, table, batch: int, seq_len: int):
    """(batch, seq_len) int32 token sequences from the teacher."""
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

    _, rest = lax.scan(step, first, jax.random.split(k1, seq_len - 1))
    return jnp.concatenate([first[None], rest], axis=0).T.astype(jnp.int32)


def batches(data_key, step: int, table, L: int, K: int, B: int, S: int):
    """Meta step ``step``'s tokens, (L, K, B, S)."""
    ks = jax.random.split(jax.random.fold_in(data_key, step), L * K)
    return jnp.stack([sample(k, table, B, S) for k in ks]).reshape(L, K, B, S)


def learning_rate(step: int, lr: float, warmup: int, total: int,
                  final_frac: float = 0.1) -> np.float32:
    if step < warmup:
        return np.float32(lr * (step + 1) / max(1, warmup))
    t = min((step - warmup) / max(1, total - warmup), 1.0)
    return np.float32(lr * (final_frac + (1 - final_frac) * 0.5
                            * (1 + np.cos(np.pi * t))))
