"""Device: share of the traced window in which no operation ran on the
device (mean over the chips)."""


def read(ctx, record):
    red = ctx.reduction
    return None if red is None else 100.0 * red.idle_share
