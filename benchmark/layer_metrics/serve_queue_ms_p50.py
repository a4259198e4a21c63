"""Serving: median time a request spent in the batcher's queue, from the
program's own marks on each request (submit to flush)."""

import numpy as np

from harness import stats


def read(ctx, record):
    q = record.get("queue_ms")
    if q is None:
        return None
    q = q[np.isfinite(q)]
    return stats.median(q.tolist()) if len(q) else None
