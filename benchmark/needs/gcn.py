"""What one training epoch of a standard-order GCN needs, from shapes alone:
the bytes and floating-point operations of the algorithm, not of any
implementation, and the rows a partition must receive.

Model of one full-batch epoch in the standard order (aggregate at the
layer's input width, then the dense layer): L layers of widths f_0 .. f_L
over V vertices and E weighted edges, the features constant.

- An aggregation pass at width f reads, for every edge, its neighbour id
  and weight (8 bytes) and the neighbour's row (f * itemsize): the tables
  here are beyond on-chip memory, so a gathered row is an HBM read; it
  writes V rows. 2 * E * f operations.
- **No pass at the input width.** Layer 1 aggregates the feature table,
  which no weight enters and no epoch changes: its aggregate is the same
  array in every epoch, so an epoch needs to read it, not to make it.
  Making it once is set-up, whatever the program does: a program that
  repeats the pass every epoch does work the epoch does not need, and its
  share of this count falls accordingly. (Until PR 27 the count priced that
  pass in every epoch, 139 of the Reddit shape's 200.7 GB, and so capped
  what a sound change could gain before reading over 100%.) The backward
  makes none there either: the features are not trained, so no gradient
  flows into them.
- Every later layer makes one pass forward and one backward: 2 (L - 1).
- A dense layer reads its input and writes its output once forward, and
  the backward reads both again for the two products (weight gradient,
  and input gradient except at layer 1, which still has its forward
  product and its weight gradient). 2 * V * f_in * f_out operations per
  product.
- Exchanges across P partitions go with the passes: one before each,
  2 (L - 1) an epoch, each bringing a device the P - 1 remote shards of
  ``vp`` padded rows. The exchange of the features is set-up with their
  aggregate.

``shape`` is what the configuration's ``inputs`` module gives: ``vertices``,
``edges``, ``layers``, ``itemsize`` and, partitioned, ``partitions`` and
``vp``. The arithmetic began as a copy of the program's tools/roofline.py
(gathered rows priced per edge) and tools/wire_accounting.py (rows per
exchange), which stay where they are and are listed in PERF.md as
superseded.
"""

from __future__ import annotations

from typing import Dict, Optional

EDGE_ENTRY_BYTES = 8  # int32 neighbour id + float32 weight


def aggregation_pass(vertices: int, edges: int, width: int, itemsize: int) -> Dict[str, float]:
    return {
        "bytes": edges * (EDGE_ENTRY_BYTES + width * itemsize) + vertices * width * itemsize,
        "flops": 2.0 * edges * width,
    }


def epoch_need(shape: dict) -> Dict[str, float]:
    """Bytes and FLOPs one training epoch needs (forward, backward; the
    optimizer's pass over the weights is negligible beside them)."""
    vertices, edges, itemsize = shape["vertices"], shape["edges"], shape["itemsize"]
    layers = shape["layers"]
    total = {"bytes": 0.0, "flops": 0.0}
    for i in range(len(layers) - 1):
        f_in, f_out = layers[i], layers[i + 1]
        passes = 0 if i == 0 else 2  # forward and backward; none at the input width
        agg = aggregation_pass(vertices, edges, f_in, itemsize)
        total["bytes"] += passes * agg["bytes"]
        total["flops"] += passes * agg["flops"]
        products = 2 if i == 0 else 3  # forward, dW, and dX except at layer 1
        total["flops"] += products * 2.0 * vertices * f_in * f_out
        total["bytes"] += products * vertices * (f_in + f_out) * itemsize
    return total


def exchange_rows_per_device(partitions: int, vp: int) -> int:
    """Remote feature rows one device receives in one dense exchange
    (all_gather or ring): P - 1 shards of vp padded rows."""
    return 0 if partitions <= 1 else (partitions - 1) * vp


def wire_rows_per_device(shape: dict) -> Optional[int]:
    """Rows one device receives per epoch: an exchange before each of the
    2 (L - 1) passes. None for a shape that is not partitioned."""
    if "partitions" not in shape:
        return None
    n_layers = len(shape["layers"]) - 1
    return (2 * n_layers - 2) * exchange_rows_per_device(shape["partitions"], shape["vp"])
