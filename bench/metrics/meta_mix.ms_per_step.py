"""Device milliseconds per meta step in the meta mix: ops under the
``obs.meta_mix`` scope (topology/base.py: the learner mean, block
momentum and learner reset), averaged over the devices."""
from bench import trace_reduce as tr


def read(trace, ctx):
    devs = tr.device_ids(trace)
    s = sum(tr.group_seconds(trace, d, lambda g: g["scope"] == "obs.meta_mix")
            for d in devs) / len(devs)
    return (1e3 * s / ctx["steps"], "ms") if s > 0 else None
