"""Exchange: device time inside collective operations (XLA's own
all-gather / all-reduce / ... ops) as a share of device busy time."""


def read(ctx, record):
    red = ctx.reduction
    return None if red is None else red.busy_percent(lambda d: d.collective_s)
