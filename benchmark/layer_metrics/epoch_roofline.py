"""Aggregation: the least time the chips could take for the bytes and
operations one epoch needs (harness/shapes.py, over the published peaks)
as a share of the device time one epoch took in the trace. The bound is
HBM bandwidth at every width these configurations have."""

from harness import shapes


def read(ctx, record):
    red = ctx.reduction
    if red is None or not record.get("epochs"):
        return None
    s = record["shape"]
    need = shapes.gcn_epoch_need(s["vertices"], s["edges"], s["layers"], s["itemsize"])
    least = shapes.least_time(need, ctx.peaks, s.get("partitions", 1))
    return 100.0 * least["seconds"] / (red.busy_s / record["epochs"])
