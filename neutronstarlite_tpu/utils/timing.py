"""Per-phase wall-clock accumulators and the DEBUGINFO-style report.

Reference: the Graph timer fields (core/graph.hpp:210-222) and each toolkit's
``DEBUGINFO()`` breakdown of compute / copy / wait / comm time
(toolkits/GCN.hpp:308-353). On TPU the async dispatch model means host-side
wall-clock only bounds a phase; for kernel-level truth use
``jax.profiler.trace`` (see neutronstarlite_tpu.utils.profiling).
"""

from __future__ import annotations

import time
from collections import defaultdict
from contextlib import contextmanager
from typing import Dict, Iterator


def get_time() -> float:
    """Monotonic seconds (reference: dep/gemini/time.hpp get_time)."""
    return time.perf_counter()


class Timer:
    """Accumulating timer: ``t.start(); ...; t.stop()`` sums elapsed time.

    Re-entrant: nested/overlapping ``start()`` calls stack their start
    times, so ``stop()`` always closes the innermost open span (a single
    ``_t0`` slot silently overwrote the outer start and corrupted totals).
    Nested same-name spans each add their own elapsed time to ``total``.
    """

    def __init__(self) -> None:
        self.total = 0.0
        self._starts: list = []
        self.count = 0

    def start(self) -> None:
        self._starts.append(get_time())

    def stop(self) -> float:
        if not self._starts:
            raise RuntimeError("Timer.stop() without a matching start()")
        dt = get_time() - self._starts.pop()
        self.total += dt
        self.count += 1
        return dt

    def reset(self) -> None:
        self.total = 0.0
        self.count = 0
        self._starts.clear()


class PhaseTimers:
    """Named phase accumulators + DEBUGINFO-style report (GCN.hpp:308-353).

    When a span tracer (obs/trace.Tracer) is attached, every ``phase()``
    interval is ALSO emitted as one ``span`` record — the aggregate report
    and the causal timeline stay two views of the same measurement instead
    of two instrumentation sites that can drift."""

    def __init__(self, tracer=None) -> None:
        self._timers: Dict[str, Timer] = defaultdict(Timer)
        self.tracer = tracer

    @contextmanager
    def phase(self, name: str, **attrs) -> Iterator[None]:
        """``attrs`` ride the span record (and, the integers, the profiler
        annotation): sizes known when the phase opens."""
        t = self._timers[name]
        t.start()
        try:
            if self.tracer is not None:
                with self.tracer.span(name, cat="phase", **attrs):
                    yield
            else:
                yield
        finally:
            t.stop()

    def total(self, name: str) -> float:
        return self._timers[name].total

    def snapshot(self) -> Dict[str, Dict[str, float]]:
        """{name: {total_s, count}} — the machine-readable twin of
        report(), consumed by the obs run_summary record."""
        return {
            name: {"total_s": t.total, "count": t.count}
            for name, t in sorted(self._timers.items())
        }

    def report(self) -> str:
        lines = ["--------------------finish algorithm !"]
        for name, t in sorted(self._timers.items()):
            avg = t.total / max(t.count, 1)
            lines.append(
                f"#{name}_time={t.total * 1000:.3f}(ms) count={t.count} avg={avg * 1000:.3f}(ms)"
            )
        return "\n".join(lines)

    def reset(self) -> None:
        for t in self._timers.values():
            t.reset()
