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
"""

from __future__ import annotations

import glob
import os
import sys
from typing import Dict

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

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
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
    cache_dir = configure_compile_cache()
    facts = device_facts()
    log.info(
        "device: platform=%s device_kind=%s count=%d | compile cache %s",
        facts["platform"], facts["device_kind"], facts["count"], cache_dir,
    )
    return facts
