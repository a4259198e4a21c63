"""Trainers: longest, over the window's epochs, from the start of the
program's ``nts:step_dispatch`` span to the start of the first operation
the device runs after it: a launch the runtime or the host held back."""

from harness import span_reduce


def read(ctx, record):
    red = ctx.reduction
    return None if red is None else span_reduce.step_launch_ms_max(red)
