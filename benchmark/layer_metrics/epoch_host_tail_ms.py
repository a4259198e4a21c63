"""Trainers: median over the window's whole epochs of the program's live
``nts:epoch`` span less its ``nts:step_dispatch`` and ``nts:step_device``:
the host's part of an epoch around the step (loss fetch, records, logits
copy, host accuracy, checkpoint hook), on the profiler's clock."""

from harness import span_reduce


def read(ctx, record):
    red = ctx.reduction
    return None if red is None else span_reduce.epoch_host_tail_ms(red)
