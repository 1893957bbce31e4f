"""Model FLOPs and kernel bytes from shapes, against a count by hand at
the reduced xlstm configuration and against XLA's count of a forward."""
from dataclasses import replace

import jax
import jax.numpy as jnp

from bench import flops

REDUCED = {"num_layers": 2, "d_model": 256, "num_heads": 4,
           "vocab_size": 512, "ssm_expand": 2, "ssm_conv": 4,
           "slstm_every": 2}
FULL = {**REDUCED, "num_layers": 24, "d_model": 1024, "vocab_size": 50304,
        "slstm_every": 4}


def test_flops_by_hand():
    # one mLSTM block (d_in 512, 4 heads of 128) and one sLSTM block (4
    # heads of 64, feed-forward 384), chunk 64
    mlstm = 2 * (256 * 2 * 512 + 3 * 512 * 512 + 2 * 512 * 4 + 512 * 256) \
        + 4 * (2 * 3 * 64 * 128 + 2 * 2 * 128 * 128)
    slstm = 2 * (4 * 256 * 256 + 4 * 4 * 64 * 64 + 256 * 2 * 384 + 384 * 256)
    head = 2 * 256 * 512
    assert mlstm + slstm + head == 4333568
    assert flops.forward_flops_per_token(REDUCED, 64) == 4333568
    assert flops.train_flops_per_token(REDUCED, 64) == 3 * 4333568


def test_full_width_counts():
    # 449M weights in matrix products (6 FLOPs each per trained token)
    # plus the chunkwise mLSTM terms
    assert round(flops.train_flops_per_token(FULL, 64) / 1e6) == 2963
    assert flops.packed_rows(FULL) == 3911776
    # read w, v, mean and write w', v' in f32, plus one bf16 learner plane
    assert flops.fused_meta_bytes(FULL, 1, 2) == 3911776 * 128 * 22


def test_packed_layout_matches_the_program():
    from repro.configs.base import get_config
    from repro.models import api
    from repro.pack import make_pack_spec

    cfg = get_config("xlstm-350m").reduced()
    shapes = jax.eval_shape(lambda k: api.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    spec = make_pack_spec(shapes)
    assert [tuple(s) for s in spec.shapes] == flops.weight_shapes(REDUCED)
    assert spec.rows == flops.packed_rows(REDUCED)


def test_flops_against_xla_cost_analysis():
    """XLA counts a loop's body once, and element-wise work besides: at
    one chunk (S = 64) and one block of each kind only the sLSTM
    recurrence over time is a loop, so its per-token term is counted once
    for the sequence."""
    from repro.configs.base import get_config
    from repro.models import api

    cfg = replace(get_config("xlstm-350m").reduced(), dtype="float32")
    params = jax.eval_shape(lambda k: api.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    B, S = 2, 64
    toks = jax.ShapeDtypeStruct((B, S), jnp.int32)
    xla = jax.jit(lambda p, t: api.forward(p, cfg, {"tokens": t})[0]).lower(
        params, toks).compile().cost_analysis()["flops"]
    recurrent = 2 * 4 * 4 * 64 * 64
    ours = flops.forward_flops_per_token(REDUCED, 64) * B * S \
        - recurrent * B * (S - 1)
    assert abs(xla / ours - 1) < 0.05
