"""Exchange: collective time during which no other operation ran on that
device (the part compute does not hide), as a share of busy time."""


def read(ctx, record):
    red = ctx.reduction
    return None if red is None else red.busy_percent(lambda d: d.collective_exposed_s)
