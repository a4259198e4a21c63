"""Funnel: seconds in the program's phase spans around the aggregation
tables: ``tables_build`` (one chip: ELL tables built and shipped),
``dist_graph_build`` and ``dist_tables_build`` (partitioned graph, sharded
tables)."""

from harness import program_spans


def read(ctx, record):
    return program_spans.seconds_of(("tables_build", "dist_graph_build", "dist_tables_build"))
