from neutronstarlite_tpu.ops.device_graph import DeviceGraph
from neutronstarlite_tpu.ops.aggregate import (
    gather_dst_from_src,
    gather_src_from_dst,
    aggregate_dst_max,
    aggregate_dst_min,
)
from neutronstarlite_tpu.ops.edge import (
    scatter_src_to_edge,
    scatter_dst_to_edge,
    scatter_src_dst_to_edge,
    aggregate_edge_to_dst,
    aggregate_edge_to_dst_weighted,
    edge_softmax,
)

# aggregation-table layouts: each pair aggregates itself, and
# gather_dst_from_src(graph, x) hands over to it (ops/aggregate.py)
from neutronstarlite_tpu.ops.blocked_ell import BlockedEllPair
from neutronstarlite_tpu.ops.ell import EllPair
from neutronstarlite_tpu.ops.ell_gat import GatEllPair, gat_ell_attention_aggregate

__all__ = [
    "DeviceGraph",
    "EllPair",
    "BlockedEllPair",
    "GatEllPair",
    "gat_ell_attention_aggregate",
    "gather_dst_from_src",
    "gather_src_from_dst",
    "aggregate_dst_max",
    "aggregate_dst_min",
    "scatter_src_to_edge",
    "scatter_dst_to_edge",
    "scatter_src_dst_to_edge",
    "aggregate_edge_to_dst",
    "aggregate_edge_to_dst_weighted",
    "edge_softmax",
]
