"""Profiling hooks: jax.profiler traces of the steady-state epochs.

Reference: manual MPI_Wtime accumulators and the DEBUGINFO() report
(core/graph.hpp:210-222, toolkits/GCN.hpp:308-353). On TPU the host-side
PhaseTimers (utils/timing.py) keep the report format, and for kernel-level
truth this module wraps ``jax.profiler`` so a run can emit a real trace
(tensorboard-compatible) when NTS_PROFILE_DIR is set.
"""

from __future__ import annotations

import os
from contextlib import contextmanager
from typing import Iterator, Optional

import jax


def profile_dir() -> Optional[str]:
    return os.environ.get("NTS_PROFILE_DIR") or None


@contextmanager
def maybe_trace(label: str = "nts") -> Iterator[None]:
    """Emit a jax.profiler trace for the enclosed region when NTS_PROFILE_DIR
    is set; no-op otherwise."""
    d = profile_dir()
    if not d:
        yield
        return
    path = os.path.join(d, label)
    os.makedirs(path, exist_ok=True)
    with jax.profiler.trace(path):
        yield
