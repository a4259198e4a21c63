"""Funnel: the benchmark's ``trainer_build_s`` (its own span around the
program's ``from_arrays``) less the program's top-level phase spans that
ended before the first epoch: construction time no phase names."""

from harness import program_spans


def read(ctx, record):
    spanned = program_spans.funnel_spanned_s()
    built = ctx.spans.get("trainer_build_s")
    return None if spanned is None or built is None else built - spanned
