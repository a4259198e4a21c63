"""Process start-up for entry points: where compiled programs are cached
and which device the process runs on.

Stock JAX honours ``JAX_PLATFORMS`` and ``jax_num_cpu_devices`` itself, so
nothing here selects a platform. Every entry point (``run``,
``serve.server``, the ``serve.crosshost`` child, the ``bench.py`` worker,
``chip_smoke.py``, the tools) calls :func:`start_runtime` once.

Naming the device initializes the backend, and the sampled trainer forks
its sampler pool before the first backend touch (sample/parallel.py). So
the entry points that build such a toolkit (``run``, ``serve``) call
:func:`configure_compile_cache` first, which touches no backend, and
:func:`start_runtime` once the toolkit is built.

The runtime's start is two spans of cat ``startup`` (docs/OBSERVABILITY.md,
Tracing): ``process_prelude``, from the start of the process to the first
line this package runs (:func:`note_package_import`), and ``backend_init``
around :func:`device_facts` in :func:`start_runtime`. One that ends before
the process has a tracer waits for the first (obs/trace ``defer``).
"""

from __future__ import annotations

import glob
import os
import sys
import time
from typing import Dict, Optional

from neutronstarlite_tpu.utils.logging import get_logger

log = get_logger("platform")

# The cache directory is part of the cache key, so it must not move between
# runs: a fixed path inside the checkout (git-ignored), never /tmp, a pid, a
# time or tempfile. ``JAX_COMPILATION_CACHE_DIR`` moves it from outside.
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def configure_compile_cache() -> str:
    """Point JAX's persistent compile cache at its directory and return it.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and no
    directory is set in code. The minimum compile time is 0 so that the AOT
    programs (``jit(...).lower().compile()`` in serve/engine.py and
    sample/fused.py), which can compile in under JAX's 1 s default, are
    cached with the rest.
    """
    import jax

    from neutronstarlite_tpu.obs import compiles

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    compiles.install()  # every compile from here on is a span (obs/compiles.py)
    return jax.config.jax_compilation_cache_dir


def device_facts() -> Dict[str, object]:
    """The device as JAX reports it (initializes the backend)."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "count": len(devices),
    }


def backend_is_live() -> bool:
    """True once a JAX backend has been initialized in this process, read
    without initializing one (the sampler pool's fork-safety gate; whether
    this process already holds the chip)."""
    if "jax" not in sys.modules:
        return False
    from jax._src import xla_bridge

    return xla_bridge.backends_are_initialized()


def process_start() -> Optional[float]:
    """When the kernel started this process, on ``time.perf_counter``'s
    clock: field 22 of ``/proc/self/stat`` (clock ticks since boot) over
    ``SC_CLK_TCK``, less what the boot clock is ahead of the monotonic one
    ``perf_counter`` reads (the time the host spent suspended; 0 on a host
    that never was). A fresh interpreter's first line reads about 0.2 s
    later. None where ``/proc`` or the clocks do not give it."""
    try:
        with open("/proc/self/stat") as fh:
            fields = fh.read().rsplit(")", 1)[1].split()  # the name may hold spaces
        suspended = time.clock_gettime(time.CLOCK_BOOTTIME) - time.clock_gettime(time.CLOCK_MONOTONIC)
        return int(fields[19]) / os.sysconf("SC_CLK_TCK") - suspended
    except (OSError, ValueError, IndexError, AttributeError):
        return None


def note_package_import(t_import: float) -> None:
    """The package's first line, once a process: ``process_prelude`` runs
    from the process's start to ``t_import``. ``backend_live``: 1 where a
    JAX backend was up already (the caller brought it up, as
    benchmark/run.py's device gate does), 0 where the program will."""
    started = process_start()
    if started is None or not 0.0 <= started <= t_import:  # not this clock's
        return
    from neutronstarlite_tpu.obs import trace

    trace.defer("process_prelude", started, t_import - started, cat="startup",
                backend_live=int(backend_is_live()))


def tpu_chip_nodes() -> int:
    """The TPU chips this host exposes as device nodes, counted without
    opening one (0: no TPU). For the places that must know whether a chip
    exists while another process may hold it."""
    return len(glob.glob("/dev/accel[0-9]*")) + len(
        glob.glob("/dev/vfio/[0-9]*")
    )


def start_runtime() -> Dict[str, object]:
    """Entry-point start-up: place the compile cache, then log one line
    naming the platform, ``device_kind`` and device count (which
    initializes the backend: call it after ``maybe_initialize_distributed``
    and after a sampler pool has forked). Returns :func:`device_facts`."""
    from neutronstarlite_tpu.obs import trace

    cache_dir = configure_compile_cache()
    t0 = time.perf_counter()
    tracer = trace.newest()
    if tracer is None:  # a tool that names the device before it builds a toolkit
        facts = device_facts()
        trace.defer("backend_init", t0, time.perf_counter() - t0, cat="startup",
                    platform=facts["platform"], count=facts["count"])
    else:
        with tracer.span("backend_init", cat="startup") as span:
            facts = device_facts()
            span.attrs.update(platform=facts["platform"], count=facts["count"])
    log.info(
        "device: platform=%s device_kind=%s count=%d | compile cache %s",
        facts["platform"], facts["device_kind"], facts["count"], cache_dir,
    )
    return facts
