"""Funnel: seconds in the program's ``datum_upload`` and
``device_graph_upload`` phase spans: host time in the placement calls of
features, labels, masks and the device graph. What a placement leaves
running shows in ``first_step_s``."""

from harness import program_spans


def read(ctx, record):
    return program_spans.seconds_of(("datum_upload", "device_graph_upload"))
