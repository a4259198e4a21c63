"""Collectors: device memory, first and warm epochs, the compiler, phases.

Each collector returns plain JSON-serializable dicts for the run_summary
record. Measured: the device's memory statistics, the epochs' times, and
what JAX reported of every compile under a span of the run (obs/compiles:
requests, cache hits and misses, seconds tracing, lowering and in the
backend). Inferred: ``compile_overhead_s``, the first epoch less the warm
median, which also holds the program's load, lazy uploads and whatever
else only the first epoch does. All of them degrade gracefully: a CPU
backend with no ``memory_stats()`` reports explicit nulls, a 1-epoch run
reports null warm statistics — telemetry never fails a run.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence


def device_memory_stats() -> Dict[str, Any]:
    """Per-device HBM accounting via ``device.memory_stats()`` where the
    backend exposes it (TPU/GPU); explicit nulls on CPU so the run_summary
    schema is identical across backends."""
    devices: List[Dict[str, Any]] = []
    try:
        import jax

        local = jax.local_devices()
    except Exception:
        local = []
    for d in local:
        try:
            ms = d.memory_stats()
        except Exception:
            ms = None
        if not ms:
            continue
        devices.append({
            "device": str(d),
            "bytes_in_use": ms.get("bytes_in_use"),
            "peak_bytes_in_use": ms.get("peak_bytes_in_use"),
            "bytes_limit": ms.get("bytes_limit"),
        })
    if not devices:
        return {
            "available": False,
            "bytes_in_use": None,
            "peak_bytes_in_use": None,
            "devices": [],
        }
    return {
        "available": True,
        "bytes_in_use": sum(int(d["bytes_in_use"] or 0) for d in devices),
        "peak_bytes_in_use": max(
            int(d["peak_bytes_in_use"] or 0) for d in devices
        ),
        "devices": devices,
    }


def steady_state_stats(epoch_times: Sequence[float]) -> Dict[str, Any]:
    """The first epoch beside the warm ones: the first carries the jit
    compile (or its cache read) and the program's load, the rest are
    steady state. ``compile_overhead_s`` is their difference, an inference;
    what the compiler took is measured (``compile_cache_info``)."""
    times = [float(t) for t in epoch_times]
    out: Dict[str, Any] = {
        "epochs": len(times),
        "first_s": times[0] if times else None,
        "warm_median_s": None,
        "warm_mean_s": None,
        "compile_overhead_s": None,
    }
    if len(times) >= 2:
        warm = sorted(times[1:])
        n = len(warm)
        med = (
            warm[n // 2] if n % 2 else 0.5 * (warm[n // 2 - 1] + warm[n // 2])
        )
        out["warm_median_s"] = med
        out["warm_mean_s"] = sum(warm) / n
        out["compile_overhead_s"] = max(times[0] - med, 0.0)
    return out


def compile_cache_info(registry) -> Dict[str, Any]:
    """Where the persistent compilation cache of this run is, and what
    the run's compiles took: the ``compile.*`` counters of ``registry``
    (obs/compiles: ``requests``, ``hits``, ``misses``, ``trace_s``,
    ``lower_s``, ``backend_s``, ``retrieve_s``; zeros in a process whose
    entry point never placed the cache, where nothing listens)."""
    from neutronstarlite_tpu.obs import compiles

    cache_dir: Optional[str] = None
    try:
        import jax

        cache_dir = jax.config.jax_compilation_cache_dir
    except Exception:
        cache_dir = None
    return {"persistent_cache_dir": cache_dir, "enabled": bool(cache_dir),
            **compiles.snapshot(registry)}


def phase_snapshot(timers) -> Dict[str, Dict[str, float]]:
    """PhaseTimers -> {name: {total_s, count}} (the DEBUGINFO host
    buckets as data instead of a printed report)."""
    if timers is None:
        return {}
    return timers.snapshot()
