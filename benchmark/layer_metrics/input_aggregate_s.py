"""Funnel: seconds in the program's ``input_aggregate`` phase span: the
standard-order GCN's layer-1 aggregate of the constant features, computed
once per run (upload of the features, the aggregation, the wait for its
result). A program without the phase reads None."""

from harness import program_spans


def read(ctx, record):
    return program_spans.seconds_of(("input_aggregate",))
