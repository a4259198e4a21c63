"""Funnel: the set-up's ``compile`` spans whose ``cache`` is ``miss``:
programs the persistent cache did not hold. 0 in a warm run."""

from harness import program_spans, setup_spans


def read(ctx, record):
    return setup_spans.cache_misses(
        setup_spans.setup_compiles(program_spans.span_records(), record["window"][0]))
