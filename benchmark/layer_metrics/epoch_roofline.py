"""Aggregation: the least time the cell's chips could take for the bytes and
operations one epoch needs (the configuration's ``need`` module, over the
published peaks) as a share of the device time one epoch took in the
trace. The bound is HBM bandwidth at every width the GCN configurations
have."""

from harness import shapes, spec


def read(ctx, record):
    red = ctx.reduction
    if red is None or not record.get("epochs"):
        return None
    need = spec.config_module(ctx.config, "need").epoch_need(record["shape"])
    least = shapes.least_time(need, ctx.peaks, ctx.chips)
    return 100.0 * least["seconds"] / (red.busy_s / record["epochs"])
