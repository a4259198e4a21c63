"""Aggregation: slots the level tables hold for each edge they carry (both
directions' padded ``rows x K`` over twice the edges; stacked tables count
every device's slots, so the ratio is one device's), from the attributes
``slots`` and ``edges`` of the program's ``tables_stats`` phase span. 1.0
is a table without padding; a padding slot is gathered, converted and
multiplied like a real one. A program without the span reads None."""

from harness import program_spans


def read(ctx, record):
    span = program_spans.first(program_spans.span_records() or [], "tables_stats")
    if span is None or not span.get("edges"):
        return None
    return float(span["slots"]) / float(span["edges"])
