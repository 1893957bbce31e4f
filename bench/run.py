"""Chip benchmark of M-AVG training: one cell of BENCHMARK.json, one run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout, on a machine that holds the chips the
cell asks for. Everything a cell needs is found by name: its entry in
BENCHMARK.json names a configuration (``bench/configs/<config>.json``, the
model and deployment) and a traffic mix (``bench/traffic/<traffic>.json``,
the training job), and ``bench/workloads/<cell>.json`` holds the limits of
its correctness check. Per-layer metrics are read by
``bench/metrics/<metric>.py``.

A run, in order:

1. set-up: build the trainer through the training launcher from the
   seed, and drive its first meta steps through ``Trainer.run``; the
   first compiles (or loads from the compilation cache in
   ``<checkout>/.jax_cache``) and the others are steady. They are also the
   steps the correctness check compares;
2. the window: one ``Trainer.run(N)`` call, N sized from the steady steps
   to last ``--seconds``, ended by ``block_until_ready``. With
   ``--trace 1`` the window is ``trace_steps`` meta steps under the JAX
   profiler instead, and the per-layer metrics are read from its trace;
3. the device's peak memory, then the program is freed;
4. the plain reference follows the compared meta steps from the same
   seed, and ``correct`` says whether the program's readings lie within
   the cell's limits.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number compared beside its
limit). Without a TPU, or with fewer chips than the cell asks for, the run
exits with code 3 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
# the program's named scopes the per-layer metrics attribute device ops to
SCOPES = ("obs.local_phase", "obs.meta_mix")
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: str = ROOT) -> dict:
    """Everything one cell needs, found by its name in BENCHMARK.json."""
    bench = _load(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no cell {name!r} in BENCHMARK.json "
                         f"(cells: {sorted(cells)})")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    entry = configs[cell["config"]]
    e2e = [m["name"] for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    per_layer = [m for m in bench["per_layer"]
                 if name in m.get("workloads", [name])
                 and m["moves"] in e2e]
    return {
        "chips": cell["chips"],
        "config": _load(os.path.join(root, entry["file"])),
        "traffic": _load(os.path.join(root, "bench", "traffic",
                                      cell["traffic"] + ".json")),
        "checks": _load(os.path.join(root, "bench", "workloads",
                                     name + ".json")),
        "end_to_end": e2e,
        "per_layer": [m["name"] for m in per_layer],
    }


def metric_reader(name: str, root: str = ROOT):
    """``read(trace, ctx)`` of ``bench/metrics/<name>.py``."""
    path = os.path.join(root, "bench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def require_chips(n: int) -> None:
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < n:
        print(f"bench/run.py: the cell needs {n} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform!r} device(s). Nothing "
              f"was run.", file=sys.stderr)
        sys.exit(3)


def enable_compile_cache() -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    every program in it however small or quick to compile, so that only
    the first run of a cell in a checkout compiles."""
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # no eviction: an environment's size cap would evict the step's
    # program between runs and make set-up compile again
    jax.config.update("jax_compilation_cache_max_size", -1)


def job_of(config: dict, traffic: dict) -> dict:
    """The training job as the reference runs it."""
    return {"learners": config["learners"], "k": traffic["k"],
            "batch": traffic["batch"], "seq": traffic["seq"],
            "lr": config["lr"], "warmup": config["lr_warmup_steps"],
            "schedule_steps": traffic["schedule_steps"],
            "momentum": config["momentum"], "meta_lr": config["meta_lr"]}


def tokens_per_step(config: dict, traffic: dict) -> int:
    return (config["learners"] * traffic["k"] * traffic["batch"]
            * traffic["seq"])


def device_info(devices) -> dict:
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices)}


def peak_bytes(devices) -> int:
    """The peak on the fullest device (0 where the backend keeps no
    statistics, as the CPU's, which only tests drive)."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def run_cell(spec: dict, seed: int, seconds: float, trace: bool,
             t_start: float = T_START, root: str = ROOT) -> dict:
    """One run of a cell; returns the result line as a dict."""
    import jax

    from bench import correctness, program, trace_reduce
    from bench.reference import mavg

    config, traffic, checks = spec["config"], spec["traffic"], spec["checks"]
    clock = program.CompileClock()
    trainer, _cfg = program.build_trainer(config, traffic, seed, trace)
    prog = program.first_steps(trainer, traffic["check_steps"], clock)
    log(f"first {traffic['check_steps']} meta steps: host seconds "
        f"{prog['step_s']}, compile seconds {prog['compile_s']}, loss "
        f"{prog['loss']}")
    step_s = min(prog["step_s"][1:])
    n = (traffic["trace_steps"] if trace
         else max(1, round(seconds / step_s)))
    devices = jax.devices()
    used = devices[:spec["chips"]]
    tmp = tempfile.mkdtemp(prefix="bench_trace_")
    compiles = clock.count
    setup_s = time.perf_counter() - t_start
    try:
        if trace:
            jax.profiler.start_trace(tmp)
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
            t0 = time.perf_counter()
            trainer.run(n, log=None)
            jax.block_until_ready(trainer.state)
            window_s = time.perf_counter() - t0
        if trace:
            jax.profiler.stop_trace()
        compiles = clock.count - compiles
        losses = [h["loss"] for h in trainer.history[-n:]]
        log(f"window losses: {losses}")
        memory_peak = peak_bytes(used)
        rate = n * tokens_per_step(config, traffic) / window_s
        log(f"window: {n} meta steps in {window_s!r} s, {rate!r} tokens/s, "
            f"{compiles} compiles in the window; set-up {setup_s!r} s; "
            f"peak device memory {memory_peak} bytes")
        reduced = None
        if trace:
            reduced = trace_reduce.reduce_dir(
                tmp, [d.id for d in used],
                [trainer.compiled_step().as_text()], SCOPES)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    del trainer
    gc.collect()

    weight_seed, salt = program.seeds(seed)
    t_ref = time.perf_counter()
    ref = mavg.run(config["model"], job_of(config, traffic), weight_seed,
                   salt, traffic["check_steps"])
    numbers = correctness.compare(prog, ref)
    limits = checks["limits"]
    correct = correctness.judge(numbers, limits)
    log(f"reference: {traffic['check_steps']} meta steps in "
        f"{time.perf_counter() - t_ref!r} s, loss {ref['loss']}; all "
        f"numbers {numbers}")

    failed = sum(not math.isfinite(x) for x in losses)
    result = {"correct": bool(correct and failed == 0), "attempted": n,
              "failed": failed, "metrics": {},
              "device": {**device_info(devices),
                         "memory_peak_bytes": memory_peak}}
    if not trace:
        e2e = {"tokens_per_s": (rate, "tokens/s"),
               "peak_hbm_gb": (memory_peak / 1e9, "GB"),
               "setup_s": (setup_s, "s")}
        result["metrics"] = {k: {"value": e2e[k][0], "unit": e2e[k][1]}
                             for k in spec["end_to_end"]}
    else:
        from bench import flops

        ctx = {"steps": n, "window_s": window_s, "tokens_per_s": rate,
               "chips": spec["chips"], "config": config, "traffic": traffic,
               "peaks": flops.peaks(devices[0].device_kind)}
        for name in spec["per_layer"]:
            got = metric_reader(name, root)(reduced, ctx)
            if got is not None:
                result["metrics"][name] = {"value": got[0], "unit": got[1]}
        busy = trace_reduce.busy_seconds(reduced)
        log("device busy seconds in the traced window, per device: "
            f"{busy}")
        result["device"]["busy_s"] = sum(busy.values()) / len(busy)
        result["device"]["window_s"] = trace_reduce.window_seconds(reduced)
        result["breakdown"] = trace_reduce.breakdown(reduced)
    result["checks"] = correctness.report(numbers, limits)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    args = ap.parse_args(argv)
    spec = load_cell(args.workload)
    require_chips(spec["chips"])
    enable_compile_cache()
    result = run_cell(spec, args.seed, args.seconds, bool(args.trace))
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
