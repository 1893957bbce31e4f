"""Benchmark harness entry point — one module per paper table/figure.

  E1 convergence.py     Figures 1-6 + Table I (M-AVG vs K-AVG, 3 models)
  E2 mu_p_sweep.py      Figures 9-12 / Lemma 6 (optimal mu grows with P)
  E3 k_sweep.py         Lemmas 5 & 7 (optimal K > 1; momentum shrinks K)
  E4 baselines.py       section IV baselines (Downpour, EAMSGD, sync)
  C  comm_bench.py      meta-communication compression (repro.comm)
  T  topology_bench.py  meta-mixing topologies x comm (repro.topology)
  L  elastic_bench.py    elastic membership / hetero-K / time-varying gossip
  A  async_bench.py      async bounded-staleness server vs the barrier
  X  chaos_bench.py      fault injection + supervised recovery (repro.chaos)
  B  robust_bench.py     Byzantine-tolerant aggregation accept (repro.robust)

Prints ``name,...`` CSV lines. ``--quick`` shrinks steps/seeds (default
here so `python -m benchmarks.run` finishes on CPU in ~15 min); pass
``--full`` for the longer runs behind DESIGN.md's tables. Every number
here comes from a CPU run: losses, samples and modeled bytes, never a
speed (the chip benchmark is ``bench/run.py``). The acceptance criteria
of the topology, async, chaos and robust suites are tier-1 tests.
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--quick", action="store_true",
                    help="explicit form of the default (smoke-sized "
                         "suites); mutually exclusive with --full")
    ap.add_argument("--only", nargs="*", default=None,
                    help="subset: convergence mu_p k baselines comm "
                         "topology elastic async chaos robust ablations")
    args = ap.parse_args()
    if args.full and args.quick:
        ap.error("--full and --quick are mutually exclusive")
    quick = not args.full

    from benchmarks import (
        ablations,
        async_bench,
        baselines,
        chaos_bench,
        comm_bench,
        convergence,
        k_sweep,
        mu_p_sweep,
        elastic_bench,
        robust_bench,
        topology_bench,
    )

    suites = {
        "comm": lambda: comm_bench.main(quick=quick),
        "topology": lambda: topology_bench.main(quick=quick),
        "elastic": lambda: elastic_bench.main(quick=quick),
        "async": lambda: async_bench.main(quick=quick),
        "chaos": lambda: chaos_bench.main(quick=quick),
        "robust": lambda: robust_bench.main(quick=quick),
        "convergence": lambda: convergence.main(quick=quick),
        "baselines": lambda: baselines.main(quick=quick),
        "k": lambda: k_sweep.main(quick=quick),
        "mu_p": lambda: mu_p_sweep.main(quick=quick),
        "ablations": lambda: ablations.main(quick=quick),
    }
    if args.only:
        suites = {k: v for k, v in suites.items() if k in args.only}

    failed = []
    for name, fn in suites.items():
        print(f"\n===== bench:{name} =====", flush=True)
        t0 = time.time()
        try:
            rows = fn()
            # sub-benchmarks report per-cell verdicts as ``ok`` fields in
            # their returned rows; a failing smoke cell must fail the
            # aggregate run even if the suite didn't raise
            bad = [r for r in (rows or []) if isinstance(r, dict)
                   and r.get("ok") is False]
            if bad:
                raise SystemExit(f"{len(bad)} cell(s) not ok")
            print(f"bench,{name},{(time.time() - t0) * 1e6:.0f},ok")
        except (Exception, SystemExit) as e:
            # SystemExit is how benches signal failed cells from main();
            # catch it so one failing suite doesn't mask the rest, then
            # exit non-zero below
            if not isinstance(e, SystemExit):
                traceback.print_exc()
            failed.append(name)
            print(f"bench,{name},{(time.time() - t0) * 1e6:.0f},FAILED ({e})")
    if failed:
        sys.exit(f"FAILED suites: {failed}")


if __name__ == "__main__":
    main()
