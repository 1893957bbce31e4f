"""Readings the correctness limits are set from, on the chip.

    python3 bench/calibrate.py --workload <cell> --seeds 12 \\
        --faults control,half_batch --fault-seeds 3 --out readings.json

For each of ``--seeds`` seeds (drawn from ``--base-seed``): the program's
first meta steps, as a run of the cell drives them in its set-up, against
the plain reference from the same seed (the lower readings). For the
first ``--fault-seeds`` of them, the reference put in the program's place
with each of ``--faults`` against the sound reference (the upper
readings): ``control`` stores the learner copies in float8 (e4m3), the
precision below the configuration's bfloat16; ``half_batch`` is a planted
fault (``bench/reference/mavg.py``). A state left unchanged reads 1 by the
gaps' measure and needs no run.

``--diagnose`` first compares the program's gradient of one batch, per
leaf, and its residual stream, block by block, with the reference's at
the same bfloat16 weights (``diagnose``).
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import statistics
import sys
import time

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]

from bench import correctness, program, run  # noqa: E402

FAULTS = {"control": {"storage": "float8_e4m3fn"},
          "half_batch": {"fault": "half_batch"}}


# (activation dtype, matmul precision) of the program's loss in the look
VARIANTS = (("bfloat16", "default"), ("bfloat16", "highest"),
            ("float32", "default"), ("float32", "highest"))


def _rms_after_blocks(blocks, x) -> list[float]:
    """The residual stream's root mean square after each block."""
    import jax.numpy as jnp

    out = []
    for f, p in blocks:
        x = f(x, p)
        out.append(float(jnp.sqrt(jnp.mean(jnp.square(
            x.astype(jnp.float32))))))
    return out


def _grad_rows(paths, got, ref) -> dict:
    import jax
    import jax.numpy as jnp

    rows = {}
    for path, a, b in zip(paths, jax.tree.leaves(got), jax.tree.leaves(ref)):
        a = a.astype(jnp.float32)
        nb = float(jnp.linalg.norm(b))
        rows[path] = {"ratio": float(jnp.linalg.norm(a)) / max(nb, 1e-30),
                      "rel_diff": float(jnp.linalg.norm(a - b))
                      / max(nb, 1e-30)}
    return rows


def diagnose(spec, seed):
    """The program's loss and gradient of one batch, and its residual
    stream block by block, against the reference's at the same bfloat16
    weights: in the configured precision, and with its activations in
    float32 and/or its matrix products at "highest" precision. Where the
    float32 "highest" program still departs from the reference on the
    chip, the reference is run again on the host's CPU as a witness."""
    import contextlib
    import dataclasses
    from functools import partial

    import jax
    import jax.numpy as jnp

    from bench.reference import data as refdata
    from bench.reference import mavg, xlstm
    from repro.launch import train
    from repro.models import api, layers
    from repro.models import xlstm as model

    config, traffic = spec["config"], spec["traffic"]
    args = train.parse_args(program.launcher_argv(config, traffic))
    cfg, _, _ = train.build(args)
    rcfg = config["model"]
    G = rcfg["num_layers"] // rcfg["slstm_every"]
    M = rcfg["slstm_every"] - 1
    ws, salt = program.seeds(seed)
    init_key, data_key = mavg.weights_and_data_keys(ws, salt)
    w = xlstm.init_weights(init_key, rcfg)
    paths = mavg.flat_paths(w)
    w16 = jax.tree.map(lambda a: a.astype(jnp.bfloat16), w)
    del w
    w16_32 = jax.tree.map(lambda a: a.astype(jnp.float32), w16)
    toks = refdata.batches(data_key, 0, refdata.teacher(cfg.vocab_size), 1,
                           1, traffic["batch"], traffic["seq"])[0, 0]
    batch = {"tokens": toks, "labels": toks}

    def blocks(params, mfn, sfn):
        out = []
        for g in range(G):
            out += [(mfn, jax.tree.map(lambda a: a[g, m], params["mlstm"]))
                    for m in range(M)]
            out.append((sfn, jax.tree.map(lambda a: a[g], params["slstm"])))
        return out

    ref_blocks = blocks(w16_32, jax.jit(partial(xlstm.mlstm_block, cfg=rcfg)),
                        jax.jit(partial(xlstm.slstm_block, cfg=rcfg)))
    ref_rms = _rms_after_blocks(ref_blocks,
                                w16_32["embed"]["embedding"][toks])
    del ref_blocks
    ref_loss, ref_grad = jax.jit(jax.value_and_grad(
        lambda p: xlstm.loss(p, toks, rcfg)))(w16_32)
    out = {"loss_reference": float(ref_loss), "rms_reference": ref_rms,
           "variants": {}}
    for dt, prec in VARIANTS:
        c = dataclasses.replace(cfg, dtype=dt)
        params = w16 if dt == "bfloat16" else w16_32
        ctx = (contextlib.nullcontext() if prec == "default"
               else jax.default_matmul_precision(prec))
        t0 = time.perf_counter()
        with ctx:
            pb = blocks(params,
                        jax.jit(lambda x, bp, c=c: model.mlstm_seq(bp, c, x)[0]),
                        jax.jit(lambda x, bp, c=c: model.slstm_seq(bp, c, x)[0]))
            rms = _rms_after_blocks(pb, layers.embed_tokens(
                params["embed"], c, toks))
            del pb
            loss, grad = jax.jit(jax.value_and_grad(
                lambda p, c=c: api.loss_fn(p, c, batch)[0]))(params)
        rows = _grad_rows(paths, grad, ref_grad)
        del grad
        out["variants"][f"{dt}/{prec}"] = {
            "loss": float(loss), "seconds": time.perf_counter() - t0,
            "rms_ratio": [a / b for a, b in zip(rms, ref_rms)],
            "leaves": rows}
        print(f"diagnose {dt}/{prec}: loss {float(loss)!r} (reference "
              f"{float(ref_loss)!r}); residual rms over the reference's by "
              f"block {[round(a / b, 4) for a, b in zip(rms, ref_rms)]}; "
              "gradient norm ratio / relative difference by leaf "
              f"{ {k: (round(v['ratio'], 4), round(v['rel_diff'], 4)) for k, v in rows.items()} }",
              flush=True)
    worst = max(v["rel_diff"] for k, v in
                out["variants"]["float32/highest"]["leaves"].items()
                if not k.endswith("b_i"))
    if worst > 1e-2:
        cpu = jax.devices("cpu")[0]
        with jax.default_device(cpu):
            loss, grad = jax.jit(jax.value_and_grad(
                lambda p: xlstm.loss(p, toks, rcfg)))(
                    jax.device_put(w16_32, cpu))
        rows = _grad_rows(paths, jax.device_put(ref_grad, cpu), grad)
        out["reference_on_cpu"] = {"loss": float(loss), "leaves": rows}
        print(f"diagnose reference on the CPU: loss {float(loss)!r}; chip "
              "over CPU gradient ratio / relative difference by leaf "
              f"{ {k: (round(v['ratio'], 4), round(v['rel_diff'], 4)) for k, v in rows.items()} }",
              flush=True)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--base-seed", type=int, default=2 ** 31 + 101)
    ap.add_argument("--faults", default="control,half_batch")
    ap.add_argument("--fault-seeds", type=int, default=3)
    ap.add_argument("--diagnose", action="store_true")
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    spec = run.load_cell(args.workload)
    run.require_chips(spec["chips"])
    run.enable_compile_cache()
    from bench.reference import mavg

    config, traffic = spec["config"], spec["traffic"]
    job = run.job_of(config, traffic)
    steps = traffic["check_steps"]
    faults = [f for f in args.faults.split(",") if f]
    out = {"workload": args.workload, "seeds": []}
    if args.diagnose:
        out["diagnose"] = diagnose(spec, args.base_seed)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    clock = program.CompileClock()
    for i in range(args.seeds):
        seed = args.base_seed + 7919 * i
        ws, salt = program.seeds(seed)
        rec = {"seed": seed}
        t0 = time.perf_counter()
        trainer, _ = program.build_trainer(config, traffic, seed)
        prog = program.first_steps(trainer, steps, clock)
        del trainer
        gc.collect()
        rec["program_s"] = time.perf_counter() - t0
        t1 = time.perf_counter()
        ref = mavg.run(config["model"], job, ws, salt, steps)
        rec["reference_s"] = time.perf_counter() - t1
        rec["reference_loss"] = ref["loss"]
        rec["program_loss"] = prog["loss"]
        rec["program"] = correctness.compare(prog, ref)
        rec["leaves"] = {k: [prog["first_grad"][k], ref["first_grad"][k],
                             prog["first_move"][k], ref["first_move"][k],
                             prog["change"][k], ref["change"][k]]
                         for k in ref["first_grad"]}
        if i < args.fault_seeds:
            for name in faults:
                t2 = time.perf_counter()
                bad = mavg.run(config["model"], job, ws, salt, steps,
                               **FAULTS[name])
                rec[name] = correctness.compare(bad, ref)
                rec[name + "_leaves"] = {k: [bad["first_grad"][k],
                                             bad["first_move"][k],
                                             bad["change"][k]]
                                         for k in ref["first_grad"]}
                rec[name + "_s"] = time.perf_counter() - t2
        out["seeds"].append(rec)
        print(json.dumps({k: v for k, v in rec.items()
                          if not k.endswith("leaves")}), flush=True)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    for who in ["program"] + faults:
        got = [r[who] for r in out["seeds"] if who in r]
        if got:
            summary = {k: [min(g[k] for g in got), statistics.median(
                g[k] for g in got), max(g[k] for g in got)]
                for k in correctness.NUMBERS}
            out.setdefault("summary", {})[who] = summary
            print(who, "min/median/max", json.dumps(summary), flush=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
