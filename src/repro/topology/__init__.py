# Meta-level mixing topologies: who averages with whom, how often
# (DESIGN.md §7). The factory is keyed on MAvgConfig.topology and composes
# with repro.comm — each edge class carries its own Reducer.
from repro.topology.async_server import (
    AsyncServer,
    resolve_async_config,
    step_time_profile,
)
from repro.topology.base import (
    FlatAllReduce,
    Topology,
    block_momentum_update,
    effective_momentum,
)
from repro.topology.elastic import (
    mask_mixing_matrix,
    membership_at,
    membership_schedule,
    present_edge_count,
)
from repro.topology.gossip import (
    Gossip,
    avg_graph_degree,
    compress_stack,
    graph_degree,
    mixing_matrix,
    mixing_matrix_stack,
    mixing_period,
)
from repro.topology.hierarchical import Hierarchical


def make_topology(cfg, reducer=None) -> Topology:
    """Build the topology described by ``cfg.topology`` (an MAvgConfig).

    ``reducer`` overrides the primary reducer (flat: the all-reduce;
    hierarchical: intra-group; gossip: neighbor exchange) — the same
    injection point meta_step/make_meta_step always exposed.

    An unset ``cfg.use_pallas`` is resolved from the platform here, once,
    so every topology and robust hook it builds sees a plain bool.
    """
    from dataclasses import replace

    from repro.kernels.ops import resolve_use_pallas

    cfg = replace(cfg, use_pallas=resolve_use_pallas(cfg.use_pallas))
    kind = cfg.topology.kind
    # the legacy downpour/eamsgd algorithms are aliases onto the async
    # bounded-staleness server (resolve_async_config) — core/meta.py has
    # no per-algorithm meta-update branches
    if kind == "async" or cfg.algorithm in ("eamsgd", "downpour"):
        return AsyncServer(cfg, reducer)
    if kind == "flat":
        return FlatAllReduce(cfg, reducer)
    if kind == "hierarchical":
        return Hierarchical(cfg, reducer)
    if kind == "gossip":
        return Gossip(cfg, reducer)
    raise ValueError(f"unknown topology {kind!r}")


__all__ = [
    "AsyncServer",
    "FlatAllReduce",
    "Gossip",
    "Hierarchical",
    "Topology",
    "avg_graph_degree",
    "block_momentum_update",
    "compress_stack",
    "effective_momentum",
    "graph_degree",
    "make_topology",
    "mask_mixing_matrix",
    "membership_at",
    "membership_schedule",
    "mixing_matrix",
    "mixing_matrix_stack",
    "mixing_period",
    "present_edge_count",
    "resolve_async_config",
    "step_time_profile",
]
