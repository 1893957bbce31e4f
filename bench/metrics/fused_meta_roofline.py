"""Share of its roofline that the fused meta-update kernel
(kernels/fused_meta.py) reaches: the least time its HBM bytes take at the
chip's peak bandwidth (bench/flops.py: it does a few FLOPs per byte, so
bandwidth bounds it) over its device time, the Pallas kernel under the
``obs.meta_mix`` scope, averaged over the devices."""
from bench import flops
from bench import trace_reduce as tr


def read(trace, ctx):
    cfg = ctx["config"]
    here = cfg["learners"] // ctx["chips"]
    need = flops.fused_meta_bytes(cfg["model"], here, 2) \
        / ctx["peaks"]["hbm_bytes_per_s"]
    devs = tr.device_ids(trace)
    took = sum(tr.group_seconds(
        trace, d, lambda g: g["kernel"] and g["scope"] == "obs.meta_mix")
        for d in devs) / len(devs) / ctx["steps"]
    return (100 * need / took, "%") if took > 0 else None
