"""Operations and bytes the work needs, from shapes alone, and the chip's
peaks (``bench/peaks.json``).

``train_flops_per_token``: the xLSTM's model FLOPs per trained token,
forward and backward (twice the forward) and no recompute. Every matrix
product of a block counts 2 FLOPs per multiply-add; the mLSTM cell counts
its chunkwise form over chunks of ``mlstm_chunk`` tokens (per token and
head: q.k, the decay-weighted values and the normaliser over the chunk,
2 x 3 x T x hd, plus reading and updating the hd x hd matrix memory,
2 x 2 x hd^2). Element-wise work (norms, gates, the sLSTM cell) is left
out: it is a few hundred FLOPs per channel against the projections'
thousands.

``fused_meta_bytes``: the HBM bytes of one call of the fused meta-update
kernel on one device: read w~, v and the learner mean, write w~' and v',
all float32 over the packed plane, and write the learner planes that
device holds in the learner dtype. The packed plane puts each weight
tensor at an offset rounded up to 128 elements, and has a multiple of 8
rows of 128.
"""
from __future__ import annotations

import json
import math
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


def peaks(device_kind: str) -> dict:
    """Per-chip peaks keyed by ``device_kind``; a kind not in the table is
    an error, not a default."""
    with open(PEAKS_FILE) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE}")
    return table[device_kind]


def _dims(m: dict):
    d, nh = m["d_model"], m["num_heads"]
    d_in = m["ssm_expand"] * d
    ff = -(-int(d * 4 / 3) // 128) * 128
    return d, nh, d_in, ff


def forward_flops_per_token(m: dict, mlstm_chunk: int) -> float:
    d, nh, d_in, ff = _dims(m)
    hd_m, hd_s = d_in // nh, d // nh
    n_s = m["num_layers"] // m["slstm_every"]
    n_m = m["num_layers"] - n_s
    mlstm = 2 * (d * 2 * d_in + 3 * d_in * d_in + 2 * d_in * nh + d_in * d)
    mlstm += nh * (2 * 3 * mlstm_chunk * hd_m + 2 * 2 * hd_m * hd_m)
    slstm = 2 * (4 * d * d + 4 * nh * hd_s * hd_s + d * 2 * ff + ff * d)
    head = 2 * d * m["vocab_size"]
    return n_m * mlstm + n_s * slstm + head


def train_flops_per_token(m: dict, mlstm_chunk: int) -> float:
    return 3 * forward_flops_per_token(m, mlstm_chunk)


def weight_shapes(m: dict) -> list[tuple]:
    """Shapes of the model's weight tensors, in the order the packed plane
    lays them out (the flattening order of the weight tree)."""
    d, nh, d_in, ff = _dims(m)
    G = m["num_layers"] // m["slstm_every"]
    M = m["slstm_every"] - 1
    hd_m, hd_s = d_in // nh, d // nh
    V = m["vocab_size"]
    mlstm = {"b_f": (nh,), "b_i": (nh,), "conv": (m["ssm_conv"], d_in),
             "norm": (d,), "out_norm": (d_in,), "w_down": (d_in, d),
             "w_f": (d_in, nh), "w_i": (d_in, nh), "w_up": (d, 2, d_in),
             "wk": (d_in, nh, hd_m), "wq": (d_in, nh, hd_m),
             "wv": (d_in, nh, hd_m)}
    slstm = {"norm": (d,), "out_norm": (d,), "w_down": (ff, d),
             "w_up": (d, 2, ff)}
    for g in "ifzo":
        slstm.update({f"b_{g}": (nh, hd_s), f"r_{g}": (nh, hd_s, hd_s),
                      f"w_{g}": (d, nh, hd_s)})
    return ([(V, d), (d, V), (d,)]
            + [(G, M) + mlstm[k] for k in sorted(mlstm)]
            + [(G,) + slstm[k] for k in sorted(slstm)])


def packed_rows(m: dict) -> int:
    off = 0
    for shape in weight_shapes(m):
        off = -(-(off + math.prod(shape)) // 128) * 128
    return -(-(off // 128) // 8) * 8


def fused_meta_bytes(m: dict, learners_here: int, learner_bytes: int) -> int:
    plane = packed_rows(m) * 128
    return plane * (5 * 4 + learners_here * learner_bytes)
