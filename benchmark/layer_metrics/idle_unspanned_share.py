"""Device: share of the window's idle time that falls under none of the
program's ``nts:`` stage spans: what the instrumentation does not cover.
The split of the idle time over the spans goes to the log."""

import json

from harness import runtime, span_reduce


def read(ctx, record):
    red = ctx.reduction
    split = None if red is None else span_reduce.idle_by_span(red)
    if split is not None:
        runtime.log("idle by span " + json.dumps([[n, round(s, 6)] for n, s in split[:12]]))
    return span_reduce.idle_unspanned_share(split)
