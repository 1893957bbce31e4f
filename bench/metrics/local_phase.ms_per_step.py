"""Device milliseconds per meta step in the local phase: ops under the
``obs.local_phase`` scope (core/meta.py), averaged over the devices."""
from bench import trace_reduce as tr


def read(trace, ctx):
    devs = tr.device_ids(trace)
    s = sum(tr.group_seconds(trace, d, lambda g: g["scope"] == "obs.local_phase")
            for d in devs) / len(devs)
    return (1e3 * s / ctx["steps"], "ms") if s > 0 else None
