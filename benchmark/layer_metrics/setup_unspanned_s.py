"""Funnel: ``setup_s`` less what the program's ``startup``, ``phase``,
``epoch``, ``stage`` and ``compile`` spans cover of it, less the
benchmark's own ``graph_s`` and ``datum_s``: set-up under no name. The
split of the set-up over the spans goes to the log."""

import json

from harness import program_spans, runtime, setup_spans

OWN = ("graph_s", "datum_s")


def read(ctx, record):
    split = setup_spans.setup_by_span(
        program_spans.span_records(), ctx.t_process_start, record["window"][0])
    if split is None:
        return None
    own = {name: ctx.spans[name] for name in OWN if name in ctx.spans}
    runtime.log("setup by span " + json.dumps([[n, round(s, 4)] for n, s in split[:24]])
                + " of which the benchmark's own " + json.dumps(own))
    return setup_spans.unspanned_s(split, sum(own.values()))
