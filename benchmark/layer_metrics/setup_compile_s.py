"""Funnel: seconds of the program's ``compile`` spans that ended before the
window's start: what JAX traced, lowered and compiled (or read from its
cache and loaded) during set-up, each cache read once and nothing of the
check. The three parts go to the log."""

import json

from harness import program_spans, runtime, setup_spans


def read(ctx, record):
    compiles = setup_spans.setup_compiles(program_spans.span_records(), record["window"][0])
    parts = setup_spans.parts(compiles)
    if parts is None:
        return None
    runtime.log(f"setup compiles {len(compiles)} spans "
                + json.dumps({k: round(v, 4) for k, v in parts.items()}))
    return sum(parts.values())
