"""Trainers: ``trace_s + lower_s`` of the ``compile`` spans under the run's
first ``epoch`` span: Python time to trace and lower the step."""

from harness import program_spans, setup_spans


def read(ctx, record):
    parts = setup_spans.parts(setup_spans.first_epoch_compiles(program_spans.span_records()))
    return None if parts is None else parts["trace_s"] + parts["lower_s"]
