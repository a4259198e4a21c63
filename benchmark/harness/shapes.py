"""What an epoch needs, computed from shapes alone: the bytes and
floating-point operations of the algorithm, not of any implementation, and
the rows a partition must receive. Roofline shares put these over the
published peaks (benchmark/peaks.json) and the device time of the trace.

Model of one full-batch GCN epoch in the standard order (aggregate at the
layer's input width, then the dense layer): L layers of widths
f_0 .. f_L over V vertices and E weighted edges.

- An aggregation pass at width f reads, for every edge, its neighbour id
  and weight (8 bytes) and the neighbour's row (f * itemsize): the tables
  here (280 MB at layer 1 of the Reddit shape) are beyond on-chip memory,
  so a gathered row is an HBM read; it writes V rows. 2 * E * f operations.
- The forward makes one pass per layer. The backward makes one per layer
  but the first: the features are not trained, so no gradient flows into
  them.
- A dense layer reads its input and writes its output once forward, and
  the backward reads both again for the two products (weight gradient,
  and input gradient except at layer 1). 2 * V * f_in * f_out operations
  per product.

Copied from the arithmetic of the program's tools/roofline.py (gathered
rows priced per edge) and tools/wire_accounting.py (rows per exchange),
which stay where they are and are listed in PERF.md as superseded.
"""

from __future__ import annotations

from typing import Dict, List

EDGE_ENTRY_BYTES = 8  # int32 neighbour id + float32 weight


def aggregation_pass(vertices: int, edges: int, width: int, itemsize: int) -> Dict[str, float]:
    return {
        "bytes": edges * (EDGE_ENTRY_BYTES + width * itemsize) + vertices * width * itemsize,
        "flops": 2.0 * edges * width,
    }


def gcn_epoch_need(vertices: int, edges: int, layers: List[int], itemsize: int) -> Dict[str, float]:
    """Bytes and FLOPs one training epoch needs (forward, backward; the
    optimizer's pass over the weights is negligible beside them)."""
    total = {"bytes": 0.0, "flops": 0.0}
    n_layers = len(layers) - 1
    for i in range(n_layers):
        f_in, f_out = layers[i], layers[i + 1]
        passes = 1 if i == 0 else 2  # forward, and backward except at layer 1
        agg = aggregation_pass(vertices, edges, f_in, itemsize)
        total["bytes"] += passes * agg["bytes"]
        total["flops"] += passes * agg["flops"]
        products = 2 if i == 0 else 3  # forward, dW, and dX except at layer 1
        total["flops"] += products * 2.0 * vertices * f_in * f_out
        total["bytes"] += products * vertices * (f_in + f_out) * itemsize
    return total


def least_time(need: Dict[str, float], peaks: dict, chips: int = 1) -> Dict[str, object]:
    """The least time ``chips`` chips could take for ``need``, and which
    peak bounds it."""
    t_hbm = need["bytes"] / (peaks["hbm_bytes_per_s"] * chips)
    t_mxu = need["flops"] / (peaks["bf16_flops_per_s"] * chips)
    return {"seconds": max(t_hbm, t_mxu), "bound": "hbm" if t_hbm >= t_mxu else "flops"}


def exchange_rows_per_device(partitions: int, vp: int) -> int:
    """Remote feature rows one device receives in one dense exchange
    (all_gather or ring): P - 1 shards of vp padded rows."""
    return 0 if partitions <= 1 else (partitions - 1) * vp


def epoch_wire_rows_per_device(partitions: int, vp: int, n_layers: int) -> int:
    """Rows received per device per epoch: one exchange per layer forward,
    and one per layer but the first backward (no gradient flows into the
    features)."""
    return (2 * n_layers - 1) * exchange_rows_per_device(partitions, vp)
