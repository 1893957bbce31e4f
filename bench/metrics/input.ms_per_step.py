"""Device milliseconds per meta step spent drawing the token batches: the
runs of the ``sample_lm`` program (data/synthetic.py) on the first
device, which draws every batch before they are placed."""
from bench import trace_reduce as tr


def read(trace, ctx):
    dev = tr.device_ids(trace)[0]
    s = tr.module_seconds(trace, dev, "jit_sample_lm")
    return (1e3 * s / ctx["steps"], "ms") if s > 0 else None
