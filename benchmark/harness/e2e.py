"""The end-to-end metrics, each from the benchmark's own clock or the
device runtime's own counters, never from a number the program computes."""

from __future__ import annotations

from typing import Callable, Dict, Optional

from . import stats


def epoch_s(ctx, record) -> Optional[float]:
    """Median over the window of the time between two epoch ends."""
    times = record.get("epoch_times")
    return stats.median(times) if times else None


def _latency_percentile(record, q: float) -> Optional[float]:
    lat = record.get("latency_ms")
    return stats.percentile(lat, q) if lat is not None and len(lat) else None


def serve_p50_ms(ctx, record) -> Optional[float]:
    return _latency_percentile(record, 50.0)


def serve_p90_ms(ctx, record) -> Optional[float]:
    """The highest percentile that repeats from run to run: the 99th is
    set by how many of the process's rare stalls of about 50 ms fall into
    the window, and is reported among the per-layer metrics, with the
    generator's lateness and the longest garbage collection beside it
    (PERF.md, section 6)."""
    return _latency_percentile(record, 90.0)


def peak_device_bytes(ctx, record) -> Optional[float]:
    peak = record.get("memory_peak_bytes")
    return float(peak) if peak else None


def setup_s(ctx, record) -> float:
    """Process start to the first measured epoch or request."""
    return record["window"][0] - ctx.t_process_start


READERS: Dict[str, Callable] = {
    f.__name__: f for f in (epoch_s, serve_p50_ms, serve_p90_ms, peak_device_bytes, setup_s)
}
