"""Device: idle share of the traced window from the start of its second
``nts:epoch`` to its end (mean over the chips): ``device_idle_share``
without the first epoch's launch."""

from harness import span_reduce


def read(ctx, record):
    red = ctx.reduction
    return None if red is None else span_reduce.steady_idle_share(red)
