"""Trainers: the program's first ``epoch`` span, the warm-up ``run()``'s
first iteration: compile or cache read, program load, lazy uploads and the
first execution."""

from harness import program_spans


def read(ctx, record):
    return program_spans.first_seconds("epoch")
