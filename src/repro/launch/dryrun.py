import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run CLI.

Lowers + compiles every (architecture x input-shape) pair against the
single-pod (16x16 = 256 chips) and multi-pod (2x16x16 = 512 chips)
production meshes, printing memory_analysis() / cost_analysis() and
writing per-combination JSON (roofline terms included) to
benchmarks/results/dryrun/.

The two lines above run before ANY other import — jax locks the device
count on first initialisation. Smoke tests / benches never import this
module, so they see 1 CPU device.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-1.7b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all --skip-existing
"""

import argparse
import json
import sys
import traceback


def main() -> int:
    from repro.configs.base import ARCH_IDS, INPUT_SHAPES
    from repro.launch import dryrun_lib as D

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", nargs="*", default=None, help="arch ids (default: all)")
    ap.add_argument("--shape", nargs="*", default=None, help="input shapes (default: all)")
    ap.add_argument("--mesh", nargs="*", default=["single", "multi"],
                    choices=["single", "multi"])
    ap.add_argument("--hierarchical", action="store_true",
                    help="pod-level learners + FSDP inside pods (multi-pod only)")
    ap.add_argument("--algorithm", default="mavg")
    ap.add_argument("--tp-mode", default="megatron",
                    choices=["megatron", "fsdp", "dp"])
    ap.add_argument("--compute-dtype", default="float32",
                    choices=["float32", "bfloat16"])
    ap.add_argument("--variant", default="",
                    help="label suffix for perf-iteration results")
    ap.add_argument("--remat", default="full",
                    choices=["full", "dots", "everything"])
    ap.add_argument("--k", type=int, default=2,
                    help="K local steps per meta-step in the lowered program")
    ap.add_argument("--expert-axis", default="",
                    help="pin MoE dispatch/combine to this mesh axis")
    ap.add_argument("--expert-shard-map", action="store_true",
                    help="manual shard_map expert parallelism (serve only)")
    ap.add_argument("--no-serve-fsdp", action="store_true",
                    help="replicate serve weights over data (perf probe)")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--save-hlo", action="store_true")
    args = ap.parse_args()

    if args.remat != "full":
        from repro.models import transformer

        transformer.set_remat_policy(args.remat)
    if args.expert_axis:
        from repro.models import moe

        moe.set_expert_axis(args.expert_axis)
    if args.no_serve_fsdp:
        from repro.launch import specs

        specs.SERVE_FSDP_ENABLED = False

    archs = args.arch or ARCH_IDS
    shapes = args.shape or list(INPUT_SHAPES)
    failures = []
    for arch in archs:
        for shape in shapes:
            for mesh in args.mesh:
                if args.hierarchical and mesh != "multi":
                    continue
                mode = "hier" if args.hierarchical else "faithful"
                if args.variant:
                    mode = f"{mode}+{args.variant}"
                path = D.result_path(arch, shape, mesh, mode, args.algorithm)
                if args.skip_existing and os.path.exists(path):
                    print(f"SKIP (exists) {arch} {shape} {mesh} {mode}")
                    continue
                print(f"=== {arch} x {shape} x {mesh} ({mode}) ===", flush=True)
                try:
                    res = D.run_one(
                        arch, shape, mesh, hierarchical=args.hierarchical,
                        algorithm=args.algorithm, save_hlo=args.save_hlo,
                        tp_mode=args.tp_mode,
                        compute_dtype=args.compute_dtype,
                        variant=args.variant,
                        k_steps=args.k,
                        expert_shard_map=args.expert_shard_map,
                    )
                except Exception as e:
                    traceback.print_exc()
                    failures.append((arch, shape, mesh, str(e)))
                    continue
                if res.get("skipped"):
                    print(f"  SKIPPED: {res['reason']}")
                else:
                    print(f"  memory_analysis: {json.dumps(res['memory'])}")
                    cost = res.get("cost", {})
                    print(
                        f"  cost_analysis: flops={cost.get('flops', 0):.3e} "
                        f"bytes={cost.get('bytes accessed', 0):.3e}"
                    )
                    print(f"  collectives: {json.dumps(res['collectives']['by_type'])}")
                    r = res["roofline"]
                    print(
                        f"  roofline: compute={r['compute_s']:.4g}s "
                        f"memory={r['memory_s']:.4g}s "
                        f"collective={r['collective_s']:.4g}s "
                        f"-> {r['bottleneck']}-bound "
                        f"(useful_ratio={r['useful_ratio']:.3f})"
                    )
                    print(f"  lower={res['lower_s']}s compile={res['compile_s']}s")
                D.save_result(res, args.algorithm)
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for f in failures:
            print(" ", f)
        return 1
    print("\nAll requested dry-run combinations lowered + compiled.")
    return 0


if __name__ == "__main__":
    sys.exit(main())
