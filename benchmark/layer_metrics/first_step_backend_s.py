"""Trainers: ``backend_s`` of the ``compile`` spans under the run's first
``epoch`` span: the compile, or the cache read and the load, of the step."""

from harness import program_spans, setup_spans


def read(ctx, record):
    parts = setup_spans.parts(setup_spans.first_epoch_compiles(program_spans.span_records()))
    return None if parts is None else parts["backend_s"]
