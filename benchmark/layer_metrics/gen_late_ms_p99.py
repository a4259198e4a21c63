"""Serving: how late the load generator sent a request after it was due,
99th percentile. A starved generator must not read as a fast server."""

from harness import stats


def read(ctx, record):
    late = record.get("late_ms")
    return stats.percentile(late.tolist(), 99.0) if late is not None and len(late) else None
