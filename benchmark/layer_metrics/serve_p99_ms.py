"""Serving: 99th percentile of the latency from due time to answer. Not
an end-to-end metric: it swings by a factor of three between runs of the
same code, with the number of process-wide stalls of about 50 ms that fall
into the window (PERF.md, section 6)."""

from harness import stats


def read(ctx, record):
    lat = record.get("latency_ms")
    return stats.percentile(lat.tolist(), 99.0) if lat is not None and len(lat) else None
