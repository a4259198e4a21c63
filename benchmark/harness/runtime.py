"""What the benchmark reads from JAX itself: the device, its memory, the
compiler's activity and the profiler."""

from __future__ import annotations

import glob
import os
import shutil
import sys
import time
from typing import List, Optional


class DeviceError(Exception):
    """JAX did not report the accelerator the cell asks for."""


def require_device(chips: int, rehearse: bool) -> dict:
    """The device as JAX reports it. Off the TPU, or with fewer chips than
    the cell asks for, this raises: there is no fallback to the CPU (a
    rehearsal asks for the CPU by name and prints no device metric)."""
    import jax

    devices = jax.devices()
    facts = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    want = "cpu" if rehearse else "tpu"
    if facts["platform"] != want or len(devices) < chips:
        raise DeviceError(
            f"the cell needs {chips} {want} device(s); JAX reports {facts}"
        )
    return facts


def memory_peak_bytes(chips: int, rehearse: bool = False) -> Optional[int]:
    """Peak bytes on the fullest of the cell's devices, for the life of the
    process: ``peak_bytes_in_use`` plus ``peak_bytes_reserved``. On this
    libtpu the first counts the buffers programs are given and return, and
    the second the scratch memory XLA reserves for the programs themselves
    (a program with 3.2 GB of temporaries moved only the second: my chip
    run, PR 22), so their sum is what the chip held. The CPU backend of a
    rehearsal keeps no such statistics; there the process's own peak
    resident set stands in, so that the record and its reader are driven as
    on the chip (a rehearsal prints names, never a value)."""
    import jax

    peaks = []
    for device in jax.devices()[:chips]:
        stats = device.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            parts = (int(stats["peak_bytes_in_use"]), int(stats.get("peak_bytes_reserved", 0)))
            peaks.append((sum(parts), parts))
    if not peaks:
        if rehearse:
            import resource

            return int(resource.getrusage(resource.RUSAGE_SELF).ru_maxrss) * 1024
        return None
    peak, (in_use, reserved) = max(peaks)
    log(f"memory peak {peak} = in use {in_use} + reserved {reserved}")
    return peak


def cpu_steal_s() -> Optional[float]:
    """Seconds the host has kept this machine's CPUs from it since boot,
    summed over the CPUs (the steal column of /proc/stat); None where the
    kernel does not say. A machine with one chip shares its host's cores."""
    try:
        with open("/proc/stat") as fh:
            fields = fh.readline().split()
    except OSError:
        return None
    if fields[:1] != ["cpu"] or len(fields) < 9:
        return None
    return int(fields[8]) / os.sysconf("SC_CLK_TCK")


class CompileLog:
    """Every request JAX makes of its compiler or its persistent cache,
    with the time it was made. ``requests_between`` is what must be zero
    over a measured window; the durations are the set-up's compile time.
    A compilation is seen as a request of the cache, as a run of the
    compiler, or (a miss) as both; with the cache off only the second
    event fires, so a window counts the larger of the two."""

    REQUEST = "/jax/compilation_cache/compile_requests_use_cache"
    COMPILE = "/jax/core/compile/backend_compile_duration"
    RETRIEVE = "/jax/compilation_cache/cache_retrieval_time_sec"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self) -> None:
        from jax import monitoring

        self.requests: List[float] = []
        self.compiles: List[float] = []
        self.hits = 0
        self.compile_s = 0.0
        self.retrieve_s = 0.0
        monitoring.register_event_listener(self._on_event)
        monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, name: str, **_) -> None:
        if name == self.REQUEST:
            self.requests.append(time.perf_counter())
        elif name == self.HIT:
            self.hits += 1

    def _on_duration(self, name: str, seconds: float, **_) -> None:
        if name == self.COMPILE:
            self.compiles.append(time.perf_counter())
            self.compile_s += seconds
        elif name == self.RETRIEVE:
            self.retrieve_s += seconds

    def requests_between(self, t0: float, t1: float) -> int:
        return max(
            sum(1 for t in stamps if t0 <= t <= t1)
            for stamps in (self.requests, self.compiles)
        )


class Profiler:
    """``jax.profiler`` around the traced window. The window itself is one
    ``TraceAnnotation`` on the host's line, which the reduction reads to
    place the window on the trace's clock. The Python tracer names what
    the host was doing in an idle gap; a mix whose host path is the
    bottleneck turns it off (``"python_tracer": false``), because it halves
    what that path sustains (the served cell refused 47% of its requests
    under it: my chip run, PR 22)."""

    WINDOW = "benchmark_window"

    def __init__(self, log_dir: str, python_tracer: bool) -> None:
        self.log_dir = log_dir
        self.python_tracer = python_tracer
        self._annotation = None

    def start(self) -> None:
        import jax

        shutil.rmtree(self.log_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = int(self.python_tracer)
        jax.profiler.start_trace(self.log_dir, profiler_options=options)
        self._annotation = jax.profiler.TraceAnnotation(self.WINDOW)
        self._annotation.__enter__()

    def stop(self) -> str:
        """Ends the window and the trace; returns the .xplane.pb path."""
        import jax

        self._annotation.__exit__(None, None, None)
        jax.profiler.stop_trace()
        found = glob.glob(
            os.path.join(self.log_dir, "plugins", "profile", "*", "*.xplane.pb")
        )
        if len(found) != 1:
            raise RuntimeError(f"want one .xplane.pb under {self.log_dir}, found {found}")
        return found[0]


def log(msg: str) -> None:
    """Progress goes to stderr; stdout's last line is the result."""
    print(f"[benchmark {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr, flush=True)
