"""Device: seconds per epoch in which an operation ran on the device
(mean over the chips), from the trace."""


def read(ctx, record):
    red = ctx.reduction
    if red is None or not record.get("epochs"):
        return None
    return red.busy_s / record["epochs"]
