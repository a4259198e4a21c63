"""Trainers: median over the window's epochs of the program's own
``step_dispatch`` span (host time to issue the epoch's device work)."""

from harness import stats


def read(ctx, record):
    spans = [s["step_dispatch"] for s in record.get("stages", []) if "step_dispatch" in s]
    return stats.median(spans) * 1000.0 if spans else None
