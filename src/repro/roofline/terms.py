"""Three-term roofline model, per chip kind (target hardware: TPU v5e).

    compute    = HLO_FLOPs        / (chips x peak_FLOP/s)
    memory     = HLO_bytes        / (chips x HBM_bw)
    collective = collective_bytes / (chips x link_bw)

HLO_FLOPs / HLO_bytes come from ``compiled.cost_analysis()`` (whole-program
totals, per the SPMD single-program view); collective bytes come from the
HLO parser in hlo.py. MODEL_FLOPS = 6 N D (dense) / 6 N_active D (MoE) is
the useful-work yardstick: HLO/MODEL ratio exposes remat recompute and
redundancy.

Meta-communication adds a fourth, *modeled* term: ``wire_bytes`` is the
payload of the per-meta-step displacement all-reduce under the configured
``repro.comm`` scheme (meta_wire_bytes), and ``wire_s`` its link time —
so the roofline table shows the compression win next to the HLO-measured
collective term.
"""
from __future__ import annotations

from dataclasses import asdict, dataclass
from typing import Optional

from repro.configs.base import CommConfig, InputShape, ModelConfig

# Per-chip peaks keyed by ``jax.devices()[i].device_kind``, each with its
# source. A device kind missing here is an error (``device_peaks``), never
# a default.
DEVICE_PEAKS = {
    "TPU v5 lite": {  # TPU v5e
        "peak_flops_bf16": 197e12,  # FLOP/s
        "hbm_bw": 819e9,  # B/s
        # fast intra-node edge class: 1,600 Gbit/s of ICI per chip over
        # 4 links
        "ici_link_bw": 50e9,  # B/s per link
        # slow inter-node edge class
        "dcn_link_bw": 25e9,  # B/s per host
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s "
                  "bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI per "
                  "chip; DCN 200 Gbit/s per host is a modeling assumption",
    },
}
TARGET_DEVICE_KIND = "TPU v5 lite"


def device_peaks(device_kind: str) -> dict:
    """The peak table entry of one chip kind; raises for an unknown one."""
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no roofline peaks for device kind {device_kind!r}; known: "
            f"{sorted(DEVICE_PEAKS)}"
        ) from None


# the target chip's peaks, for the modeled (no-device) tables
_TARGET = device_peaks(TARGET_DEVICE_KIND)
PEAK_FLOPS_BF16 = _TARGET["peak_flops_bf16"]
HBM_BW = _TARGET["hbm_bw"]
ICI_LINK_BW = _TARGET["ici_link_bw"]
DCN_LINK_BW = _TARGET["dcn_link_bw"]


@dataclass
class RooflineTerms:
    arch: str
    shape: str
    mesh: str
    chips: int
    hlo_flops: float
    hlo_bytes: float
    collective_bytes: float
    model_flops: float
    compute_s: float
    memory_s: float
    collective_s: float
    bottleneck: str
    useful_ratio: float  # MODEL_FLOPS / HLO_FLOPs
    # modeled meta-communication (repro.comm); 0 / "dense" when not computed
    wire_bytes: float = 0.0
    wire_s: float = 0.0
    comm_scheme: str = "dense"
    # per-edge-class split (repro.topology): intra-node (ICI) vs
    # inter-node (DCN) payload per meta step, amortized over outer_every
    wire_intra_bytes: float = 0.0
    wire_inter_bytes: float = 0.0
    topology: str = "flat"

    def to_dict(self):
        return asdict(self)


def participant_wire_bytes(n_params: int, comm: Optional[CommConfig], *,
                           learner_bytes: int = 4) -> float:
    """Payload ONE participant ships under ``comm`` (per meta round).

    Analytic model matching repro.comm's per-step accounting (the
    bytes-per-value/scale/index constants are imported from there so the
    two can't drift); scales are one f32 per chunk_rows x 128 values.
    """
    from repro.comm.quant import SCALE_BYTES, VALUE_BYTES
    from repro.comm.topk import INDEX_BYTES

    if comm is None or comm.scheme == "dense":
        return float(n_params * learner_bytes)
    n_chunks = max(1.0, n_params / (comm.chunk_rows * 128))
    if comm.scheme in VALUE_BYTES:
        per = n_params * VALUE_BYTES[comm.scheme] + n_chunks * SCALE_BYTES
    elif comm.scheme == "topk":
        per = comm.k_frac * n_params * (learner_bytes + INDEX_BYTES)
    elif comm.scheme == "int8_topk":
        per = (comm.k_frac * n_params * (VALUE_BYTES["int8"] + INDEX_BYTES)
               + n_chunks * SCALE_BYTES)
    else:
        raise ValueError(f"unknown comm scheme {comm.scheme!r}")
    return float(per)


def meta_wire_bytes(n_params: int, comm: Optional[CommConfig], *,
                    num_learners: int, learner_bytes: int = 4) -> tuple[float, float]:
    """(dense_bytes, wire_bytes) of one *flat* meta averaging round:
    every learner ships its (possibly compressed) displacement."""
    dense = float(num_learners * n_params * learner_bytes)
    wire = num_learners * participant_wire_bytes(
        n_params, comm, learner_bytes=learner_bytes
    )
    return dense, wire


def elastic_presence(topology, num_learners: int) -> tuple[float, float]:
    """(learner_frac, edge_frac) expected under the membership schedule.

    ``learner_frac`` is the mean fraction of learners present per meta
    step; ``edge_frac`` the mean fraction of *graph edges* with both
    endpoints present — for gossip the two differ (an edge dies when
    either endpoint is absent), and for time-varying graphs the live-edge
    count is averaged over the combined schedule x graph period. Both are
    1.0 when elasticity is off.
    """
    import math

    if topology is None or getattr(topology, "elastic", None) is None:
        return 1.0, 1.0
    from repro.topology import membership_schedule, mixing_matrix_stack

    import numpy as np

    groups = topology.groups if topology.kind == "hierarchical" else 1
    sched = membership_schedule(num_learners, topology.elastic, groups=groups)
    learner_frac = float(sched.mean())
    if topology.kind != "gossip":
        return learner_frac, learner_frac
    stack = mixing_matrix_stack(topology.graph, num_learners)
    T_g, T_s = stack.shape[0], sched.shape[0]
    eye = np.eye(num_learners, dtype=bool)
    tot = live = 0.0
    for t in range(math.lcm(T_g, T_s)):
        adj = (stack[t % T_g] > 0) & ~eye
        m = sched[t % T_s]
        tot += adj.sum()
        live += (adj & (m[:, None] > 0) & (m[None, :] > 0)).sum()
    return learner_frac, float(live / max(tot, 1.0))


def topology_wire_bytes(n_params: int, comm: Optional[CommConfig],
                        topology, *, num_learners: int,
                        learner_bytes: int = 4) -> dict:
    """Per-edge-class wire model of one meta iteration (amortized).

    Returns {"intra_bytes", "inter_bytes", "total_bytes"} plus the
    degree-over-time inputs ("avg_degree", "learner_presence",
    "edge_presence") — bytes crossing the fast intra-node links vs the
    slow inter-node links per meta step, under the given
    ``TopologyConfig`` (None -> flat):

    flat          every learner's displacement feeds a global all-reduce —
                  all of it is modeled as inter-node (the paper's worst
                  case, what K amortizes)
    hierarchical  L intra-group payloads (inner_comm) every step, scaled
                  by the membership presence fraction under elasticity; G
                  cross-group payloads (outer_comm) every outer_every
                  steps, amortized
    gossip        every learner ships to each of its live graph edges
                  every step — inter-node, no amortization; the degree is
                  averaged over the graph period (one-peer exponential)
                  and edges die when either endpoint is absent
    async         push-when-ready: learner j ships its (dense) plane once
                  per step_time[j]-tick block, so the per-tick inter
                  payload is sum_j per / m_j — the staleness profile
                  amortizes the wire exactly the way it skews the clocks
    """
    L = num_learners
    per = lambda c: participant_wire_bytes(n_params, c,
                                           learner_bytes=learner_bytes)
    avg_deg = 0.0
    learner_frac, edge_frac = elastic_presence(topology, L)
    if topology is None or topology.kind == "flat":
        inter = L * per(comm)
        intra = 0.0
    elif topology.kind == "hierarchical":
        intra = L * per(topology.inner_comm or comm) * learner_frac
        inter = (topology.groups * per(topology.outer_comm or comm)
                 / topology.outer_every)
    elif topology.kind == "gossip":
        from repro.topology import avg_graph_degree

        avg_deg = avg_graph_degree(topology.graph, L)
        intra = 0.0
        inter = L * avg_deg * per(topology.inner_comm or comm) * edge_frac
    elif topology.kind == "async":
        from repro.configs.base import AsyncConfig
        from repro.topology import step_time_profile

        acfg = topology.server if topology.server is not None else AsyncConfig()
        prof = step_time_profile(L, acfg)
        pushes_per_tick = float((1.0 / prof).sum())
        intra = 0.0
        # the async server ships dense displacement planes (enforced at
        # config time), one per firing learner per tick
        inter = per(comm) * pushes_per_tick * learner_frac
    else:
        raise ValueError(f"unknown topology {topology.kind!r}")
    return {"intra_bytes": float(intra), "inter_bytes": float(inter),
            "total_bytes": float(intra + inter),
            "avg_degree": float(avg_deg),
            "learner_presence": learner_frac, "edge_presence": edge_frac}


def model_flops(cfg: ModelConfig, shape: InputShape, k_steps: int = 1) -> float:
    """6 N D per processed token (training) or 2 N D (inference forward)."""
    n = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len * k_steps
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens
    # decode: one token per sequence
    return 2.0 * n * shape.global_batch


def compute_terms(*, arch: str, shape: InputShape, mesh_name: str, chips: int,
                  hlo_flops: float, hlo_bytes: float, collective_bytes: float,
                  cfg: ModelConfig, k_steps: int = 1,
                  per_device: bool = True, comm: Optional[CommConfig] = None,
                  num_learners: int = 1, topology=None,
                  device_kind: str = TARGET_DEVICE_KIND) -> RooflineTerms:
    """per_device=True: the HLO numbers come from the SPMD-partitioned
    module, i.e. they are already per-chip (this is what
    ``compiled.as_text()`` exposes). The spec formula X/(chips*rate) with
    whole-program X is identical to X_per_device/rate. The rates are
    ``device_kind``'s row of ``DEVICE_PEAKS``."""
    pk = device_peaks(device_kind)
    mf = model_flops(cfg, shape, k_steps)
    div = 1 if per_device else chips
    compute_s = hlo_flops / (div * pk["peak_flops_bf16"])
    memory_s = hlo_bytes / (div * pk["hbm_bw"])
    collective_s = collective_bytes / (div * pk["ici_link_bw"])
    terms = {"compute": compute_s, "memory": memory_s, "collective": collective_s}
    bottleneck = max(terms, key=terms.get)
    mf_dev = mf / chips if per_device else mf
    wire_bytes = wire_s = intra_b = inter_b = 0.0
    if comm is not None or topology is not None:
        edge = topology_wire_bytes(
            cfg.param_count(), comm, topology, num_learners=num_learners
        )
        intra_b, inter_b = edge["intra_bytes"], edge["inter_bytes"]
        wire_bytes = edge["total_bytes"]
        # each edge class rides its own fabric
        wire_s = (intra_b / (chips * pk["ici_link_bw"])
                  + inter_b / (chips * pk["dcn_link_bw"]))
    return RooflineTerms(
        arch=arch,
        shape=shape.name,
        mesh=mesh_name,
        chips=chips,
        hlo_flops=hlo_flops,
        hlo_bytes=hlo_bytes,
        collective_bytes=collective_bytes,
        model_flops=mf,
        compute_s=compute_s,
        memory_s=memory_s,
        collective_s=collective_s,
        bottleneck=bottleneck,
        useful_ratio=mf_dev / hlo_flops if hlo_flops else 0.0,
        wire_bytes=wire_bytes,
        wire_s=wire_s,
        comm_scheme=comm.scheme if comm is not None else "dense",
        wire_intra_bytes=intra_b,
        wire_inter_bytes=inter_b,
        topology=topology.kind if topology is not None else "flat",
    )
