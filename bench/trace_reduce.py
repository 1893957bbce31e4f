"""From a JAX profiler trace of the window to the intervals the per-layer
metrics read.

``read_xplane`` reads the ``.xplane.pb`` the profiler wrote: on each traced
device the ``XLA Ops`` line (one event per executed HLO instruction) and
the ``XLA Modules`` line (one event per program run), and on the host the
``TraceAnnotation`` spans of the Python thread. An op is put in a group by
its HLO instruction: the ``op_name`` that ``jax.named_scope`` left in the
compiled program's metadata (``scopes_from_hlo``), whether it is a Pallas
kernel (``tpu_custom_call``) and whether it is a loop op that spans the
ops of its body.

The reduced trace is a plain dict, all times in nanoseconds on the
trace's clock::

    {"window": [t0, t1],                      # the bench.window host span
     "groups": [{"name", "scope", "kernel", "container"}, ...],
     "devices": {"0": {"ops": [[start, end, group], ...],
                       "modules": [[start, end, name], ...]}, ...},
     "host": [[start, end, name], ...]}

and the functions below reduce it: the union of intervals a device was
busy, the part of it spent in ops of one scope, and the idle gaps
labelled by the host span that covers them.
"""
from __future__ import annotations

import glob
import re
from collections import defaultdict

import numpy as np

WINDOW_SPAN = "bench.window"
_INSTR = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = (.*)$", re.M)
_OP_NAME = re.compile(r'op_name="([^"]*)"')
# ops whose event spans the ops of the computations they run: counted
# through those, never themselves
CONTAINERS = ("while", "conditional", "call")


def _opcode(rest: str) -> str:
    """The opcode of an HLO instruction from the text after ' = ': the
    result type (a tuple type in parentheses, or up to the first space),
    then the opcode up to its operands."""
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        tail = rest[i + 1:].lstrip()
    else:
        tail = rest[rest.find(" ") + 1:]
    return tail[:tail.find("(")]


def scopes_from_hlo(texts) -> dict[str, dict]:
    """HLO instruction name -> {"op_name", "opcode", "kernel"} over the
    compiled programs' texts."""
    out = {}
    for text in texts:
        for name, rest in _INSTR.findall(text):
            m = _OP_NAME.search(rest)
            out[name] = {"op_name": m.group(1) if m else "",
                         "opcode": _opcode(rest),
                         "kernel": 'custom_call_target="tpu_custom_call"'
                                   in rest}
    return out


def _group_of(event_name: str, scopes: dict, scope_names) -> dict:
    instr = event_name[1:event_name.find(" = ")] \
        if event_name.startswith("%") and " = " in event_name \
        else event_name.split(" ")[0]
    base = re.sub(r"\.\d+$", "", instr)
    info = scopes.get(instr, {"op_name": "", "opcode": base,
                              "kernel": False})
    op_name = info["op_name"]
    scope = next((s for s in scope_names if s in op_name), "")
    tail = "/".join(op_name.split("/")[-2:]) if op_name else base
    return {"name": f"{scope or 'unscoped'}: {tail}", "scope": scope,
            "kernel": info["kernel"],
            "container": info["opcode"] in CONTAINERS}


def read_xplane(path: str, device_ids, scopes: dict, scope_names) -> dict:
    """The reduced trace of one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    groups, index = [], {}
    devices, host = {}, []
    for plane in pd.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m and int(m.group(1)) in device_ids:
            ops, modules = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for e in line.events:
                        g = index.get(e.name)
                        if g is None:
                            info = _group_of(e.name, scopes, scope_names)
                            key = tuple(info.values())
                            g = index.setdefault(key, len(groups))
                            if g == len(groups):
                                groups.append(info)
                            index[e.name] = g
                        ops.append((e.start_ns, e.end_ns, g))
                elif line.name == "XLA Modules":
                    modules += [[e.start_ns, e.end_ns,
                                 e.name.split("(")[0]] for e in line.events]
            devices[m.group(1)] = {
                "ops": np.array(ops, dtype=np.float64).reshape(-1, 3),
                "modules": modules}
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(("obs.", "bench.")):
                        host.append([e.start_ns, e.end_ns, e.name])
    window = [s for s in host if s[2] == WINDOW_SPAN]
    if not window:
        raise ValueError(f"no {WINDOW_SPAN!r} span in {path}")
    return {"window": window[0][:2], "groups": groups, "devices": devices,
            "host": sorted(host)}


def reduce_dir(trace_dir: str, device_ids, hlo_texts, scope_names) -> dict:
    paths = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    if len(paths) != 1:
        raise ValueError(f"expected one .xplane.pb under {trace_dir}, "
                         f"found {paths}")
    return read_xplane(paths[0], set(device_ids),
                       scopes_from_hlo(hlo_texts), scope_names)


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------


def _arrays(trace, dev, keep=None):
    """(starts, ends) of the device's ops that ``keep`` selects by group,
    never the containers."""
    ops = np.asarray(trace["devices"][dev]["ops"], dtype=np.float64)
    leaf = ~_mask(trace, lambda g: g["container"])
    keep = leaf if keep is None else keep & leaf
    if ops.size == 0:
        return np.zeros(0), np.zeros(0)
    ops = ops[keep[ops[:, 2].astype(np.int64)]]
    return ops[:, 0], ops[:, 1]


def union(starts, ends):
    """Sorted disjoint intervals covering the given ones."""
    if len(starts) == 0:
        return np.zeros(0), np.zeros(0)
    o = np.argsort(starts, kind="stable")
    s, e = starts[o], np.maximum.accumulate(ends[o])
    brk = np.nonzero(s[1:] > e[:-1])[0]
    return np.r_[s[0], s[brk + 1]], np.r_[e[brk], e[-1]]


def covered(starts, ends) -> float:
    s, e = union(starts, ends)
    return float(np.sum(e - s))


def _clip(trace, starts, ends):
    t0, t1 = trace["window"]
    return np.clip(starts, t0, t1), np.clip(ends, t0, t1)


def device_ids(trace):
    return sorted(trace["devices"], key=int)


def busy_seconds(trace) -> dict[str, float]:
    """Per device, the union of its ops' intervals inside the window."""
    return {d: covered(*_clip(trace, *_arrays(trace, d))) / 1e9
            for d in device_ids(trace)}


def window_seconds(trace) -> float:
    t0, t1 = trace["window"]
    return (t1 - t0) / 1e9


def _mask(trace, pred):
    return np.array([bool(pred(g)) for g in trace["groups"]], dtype=bool)


def group_seconds(trace, dev, pred) -> float:
    """Seconds in which an op of the groups ``pred`` selects ran."""
    return covered(*_clip(trace, *_arrays(trace, dev,
                                          _mask(trace, pred)))) / 1e9


def module_seconds(trace, dev, prefix: str) -> float:
    """Device seconds of the runs of programs whose name starts with
    ``prefix`` (one program runs at a time on a device)."""
    mods = [m for m in trace["devices"][dev]["modules"]
            if m[2].startswith(prefix)]
    if not mods:
        return 0.0
    s = np.array([m[0] for m in mods], dtype=np.float64)
    e = np.array([m[1] for m in mods], dtype=np.float64)
    return covered(*_clip(trace, s, e)) / 1e9


def idle_gaps(trace, dev):
    """(start, end) of each interval in the window with no op running."""
    t0, t1 = trace["window"]
    s, e = union(*_clip(trace, *_arrays(trace, dev)))
    starts = np.r_[t0, e]
    ends = np.r_[s, t1]
    keep = ends > starts
    return list(zip(starts[keep], ends[keep]))


def _host_label(trace, mid) -> str:
    best = None
    for s, e, name in trace["host"]:
        if s <= mid <= e and name != WINDOW_SPAN:
            if best is None or e - s < best[1] - best[0]:
                best = (s, e, name)
    return best[2] if best else "no span"


def breakdown(trace, top: int = 10) -> dict:
    """The device ops that took most time (seconds per op group, averaged
    over the devices) and the longest idle gaps, each named by the host
    span that covers its middle, on the first device."""
    per = defaultdict(float)
    devs = device_ids(trace)
    for d in devs:
        ops = np.asarray(trace["devices"][d]["ops"], dtype=np.float64)
        if ops.size:
            sums = np.bincount(ops[:, 2].astype(np.int64),
                               weights=ops[:, 1] - ops[:, 0],
                               minlength=len(trace["groups"]))
            for g, t in enumerate(sums):
                if not trace["groups"][g]["container"]:
                    per[trace["groups"][g]["name"]] += t / 1e9 / len(devs)
    ops = sorted(per.items(), key=lambda x: -x[1])[:top]
    gaps = sorted(idle_gaps(trace, devs[0]), key=lambda g: g[0] - g[1])[:top]
    return {"device_ops": [[n, t] for n, t in ops],
            "idle_gaps": [[_host_label(trace, (s + e) / 2), (e - s) / 1e9]
                          for s, e in gaps]}
