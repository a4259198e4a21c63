"""Funnel: seconds JAX spent in its compiler and in reading its persistent
cache during the run (its own monitoring events). Near zero from the
second run of a cell in a checkout."""


def read(ctx, record):
    return ctx.compile_log.compile_s + ctx.compile_log.retrieve_s
