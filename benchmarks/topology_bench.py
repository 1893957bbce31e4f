"""Topology benchmark: convergence + per-edge-class bytes-on-wire for the
repro.topology subsystem, sweeping topology x comm scheme.

Two layers of numbers, mirroring comm_bench.py:

1. *Measured* — final loss / val accuracy of the teacher-classification
   MLP under each (topology, comm) cell at equal meta-iterations, plus
   the topology's own per-step comm metrics. The acceptance rows: the
   hierarchical cell with int8_topk cross-group traffic must ship >= 50x
   fewer modeled inter-node bytes than flat dense while landing within
   25% of flat mavg's final loss.
2. *Modeled* — roofline.topology_wire_bytes on a full-scale config
   (qwen3-1.7b): per-meta-step intra-node (ICI) vs inter-node (DCN)
   payloads and link times per topology at production size.

Prints ``topo,...`` CSV lines; ``--json PATH`` additionally dumps every
row as JSON. ``--smoke`` shrinks steps; tests/test_topology.py (T8) holds
the acceptance rows at that size.
"""
from __future__ import annotations

import argparse
import os
import sys

if __package__ in (None, ""):  # `python benchmarks/topology_bench.py --smoke`
    _root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(_root, "src"))
    sys.path.insert(0, _root)

from benchmarks.common import run_mlp
from repro.configs.base import CommConfig, TopologyConfig, get_config
from repro.roofline import DCN_LINK_BW, ICI_LINK_BW, topology_wire_bytes

P, K, MU = 8, 4, 0.7

# the sweep: name -> (TopologyConfig, CommConfig) cells
CELLS = (
    ("flat_dense", TopologyConfig(), CommConfig()),
    ("flat_int8", TopologyConfig(),
     CommConfig(scheme="int8", error_feedback=True)),
    # mu_out = 0 on purpose: the inner level already carries the block
    # momentum, and stacking a second momentum on the outer displacement
    # over-accelerates on this problem (mu_out=0.5 diverges; the knob
    # stays exercised by the tests)
    ("hier_dense", TopologyConfig(kind="hierarchical", groups=2,
                                  outer_every=2),
     CommConfig()),
    # the acceptance cell: dense intra-group, int8_topk cross-group
    ("hier_int8topk_outer",
     TopologyConfig(kind="hierarchical", groups=2, outer_every=2,
                    outer_comm=CommConfig(scheme="int8_topk",
                                          error_feedback=True)),
     CommConfig()),
    ("gossip_ring", TopologyConfig(kind="gossip", graph="ring"), CommConfig()),
    ("gossip_exp_mt", TopologyConfig(kind="gossip", graph="exponential",
                                     momentum_tracking=True), CommConfig()),
)


def measured(quick: bool) -> list[dict]:
    steps = 20 if quick else 80
    rows, flat_loss = [], None
    for name, topo, comm in CELLS:
        losses, acc = run_mlp("mavg", P=P, K=K, mu=MU, steps=steps,
                              comm=comm, topology=topo)
        final = sum(losses[-5:]) / len(losses[-5:])
        if name == "flat_dense":
            flat_loss = final
        # modeled per-edge-class bytes on the MLP-sized problem are noise;
        # report the full-scale model instead (see modeled()) and keep the
        # measured rows about convergence quality
        row = {
            "kind": "topo_measured", "cell": name,
            "topology": topo.kind, "graph": topo.graph,
            "groups": topo.groups, "outer_every": topo.outer_every,
            "final_loss": final, "vs_flat": final / flat_loss,
            "val_acc": acc, "meta_steps": steps,
        }
        rows.append(row)
        print(f"topo,{name},final_loss,{final:.4f},{final / flat_loss:.3f}x_flat")
        print(f"topo,{name},val_acc,{acc:.3f},frac")
    return rows


def modeled(arch: str = "qwen3-1.7b", num_learners: int = P) -> list[dict]:
    n = get_config(arch).param_count()
    rows = []
    for name, topo, comm in CELLS:
        edge = topology_wire_bytes(n, comm, topo, num_learners=num_learners)
        wire_s = (edge["intra_bytes"] / ICI_LINK_BW
                  + edge["inter_bytes"] / DCN_LINK_BW)
        row = {
            "kind": "topo_model", "cell": name, "arch": arch,
            **edge, "wire_s": wire_s,
        }
        rows.append(row)
        print(f"topo_model,{arch},{name},intra,{edge['intra_bytes']:.3e},B,"
              f"inter,{edge['inter_bytes']:.3e},B,{wire_s:.4f},s")
    flat = next(r for r in rows if r["cell"] == "flat_dense")
    hier = next(r for r in rows if r["cell"] == "hier_int8topk_outer")
    ratio = flat["inter_bytes"] / max(hier["inter_bytes"], 1.0)
    rows.append({"kind": "topo_accept", "arch": arch,
                 "inter_reduction_vs_flat": ratio})
    print(f"topo_accept,{arch},inter_reduction,{ratio:.1f},x")
    return rows


def main(quick: bool = False, json_path: str | None = None) -> list[dict]:
    rows = measured(quick) + modeled()
    if json_path:
        from benchmarks.common import write_rows

        write_rows(json_path, rows, suite="topology_bench")
        print(f"wrote {len(rows)} rows to {json_path}")
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="few steps (CI)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also dump all rows as JSON")
    args = ap.parse_args()
    main(quick=args.smoke, json_path=args.json)
