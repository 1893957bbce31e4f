"""Chunkwise-parallel mLSTM == step-recurrent mLSTM (xlstm-350m trains in
the chunkwise form: the recurrent form's backward does not fit one chip,
PERF.md section 6). Exactness matters: the chunked
form is used for training, the recurrent form for decode, and they must
agree or train/serve diverge."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import xlstm

CFG = dataclasses.replace(get_config("xlstm-350m").reduced(), dtype="float32")
RNG = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def block():
    return xlstm._init_mlstm_block(RNG, CFG)


@pytest.mark.parametrize("chunk", [8, 16, 64])
@pytest.mark.parametrize("seq", [64, 128])
def test_chunked_matches_recurrent(block, chunk, seq):
    if seq % chunk:
        pytest.skip("chunk must divide seq")
    x = jax.random.normal(jax.random.PRNGKey(1), (2, seq, CFG.d_model)) * 0.5
    out_r, st_r = xlstm.mlstm_recurrent(block, CFG, x)
    out_c, st_c = xlstm.mlstm_chunked(block, CFG, x, chunk=chunk)
    np.testing.assert_allclose(np.asarray(out_c), np.asarray(out_r),
                               rtol=3e-4, atol=3e-4)
    for a, b, nm in zip(st_c[:3], st_r[:3], ("C", "n", "m")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-3, atol=3e-3, err_msg=nm)


def test_chunked_continuation(block):
    """State handoff across calls (train-time TBPTT / decode warm start)."""
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 64, CFG.d_model)) * 0.5
    _, st = xlstm.mlstm_recurrent(block, CFG, x)
    out_r, _ = xlstm.mlstm_recurrent(block, CFG, x, st)
    out_c, _ = xlstm.mlstm_chunked(block, CFG, x, st, chunk=16)
    np.testing.assert_allclose(np.asarray(out_c), np.asarray(out_r),
                               rtol=3e-4, atol=3e-4)


def test_full_model_with_chunking(block, monkeypatch):
    """End-to-end forward equality: a length that divides by the chunk
    takes the chunkwise form by default; chunk 0 forces the recurrent
    form."""
    params = xlstm.init(RNG, CFG)
    toks = jax.random.randint(RNG, (2, 2 * xlstm.MLSTM_CHUNK), 0,
                              CFG.vocab_size, jnp.int32)
    logits_chk, _ = xlstm.forward(params, CFG, {"tokens": toks})
    monkeypatch.setattr(xlstm, "MLSTM_CHUNK", 0)
    logits_rec, _ = xlstm.forward(params, CFG, {"tokens": toks})
    np.testing.assert_allclose(np.asarray(logits_chk), np.asarray(logits_rec),
                               rtol=3e-3, atol=3e-3)
