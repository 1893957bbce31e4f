"""The whole meta step's share of the chips' peak: the model FLOPs per
trained token (bench/flops.py, forward and backward, no recompute) times
the traced window's tokens per second, over the chips' bf16 peak."""
from bench import flops


def read(trace, ctx):
    cfg = ctx["config"]
    per_token = flops.train_flops_per_token(cfg["model"], cfg["mlstm_chunk"])
    peak = ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"]
    return 100 * per_token * ctx["tokens_per_s"] / peak, "%"
