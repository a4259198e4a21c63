"""obs/collectors direct coverage on the CPU-only rig.

The collectors were previously exercised only incidentally through trainer
smokes; these tests pin their contracts standalone: graceful degradation
(CPU backends expose no memory_stats -> explicit nulls, 0/1-epoch runs ->
null warm statistics), the first-against-warm arithmetic, and the
compiler's snapshot (where the persistent cache is, and the run's
``compile.*`` counters) — so a collector regression fails HERE with a
named cause instead of somewhere inside a 40-second smoke.
"""

from __future__ import annotations

import pytest

from neutronstarlite_tpu.obs import collectors, compiles, registry
from neutronstarlite_tpu.utils.timing import PhaseTimers


# ---- device_memory_stats ----------------------------------------------------


def test_device_memory_stats_shape_is_backend_independent():
    """One schema either way: 'available' bool + the three aggregate keys;
    on the CPU rig (no memory_stats) the values are explicit nulls."""
    mem = collectors.device_memory_stats()
    assert isinstance(mem["available"], bool)
    assert set(mem) >= {"available", "bytes_in_use", "peak_bytes_in_use",
                        "devices"}
    assert isinstance(mem["devices"], list)
    if not mem["available"]:
        assert mem["bytes_in_use"] is None
        assert mem["peak_bytes_in_use"] is None
        assert mem["devices"] == []
    else:  # a rig that DOES expose stats must aggregate them as ints
        assert isinstance(mem["bytes_in_use"], int)
        assert isinstance(mem["peak_bytes_in_use"], int)
        for d in mem["devices"]:
            assert "device" in d and "bytes_in_use" in d


def test_device_memory_stats_survives_broken_jax(monkeypatch):
    """Telemetry must never fail a run: a jax whose local_devices() raises
    degrades to the explicit-null shape instead of propagating."""
    import jax

    monkeypatch.setattr(
        jax, "local_devices",
        lambda *a, **k: (_ for _ in ()).throw(RuntimeError("boom")),
    )
    mem = collectors.device_memory_stats()
    assert mem["available"] is False and mem["devices"] == []


# ---- steady_state_stats -----------------------------------------------------


def test_steady_state_stats_empty_and_single():
    z = collectors.steady_state_stats([])
    assert z["epochs"] == 0 and z["first_s"] is None
    assert z["warm_median_s"] is None and z["compile_overhead_s"] is None

    one = collectors.steady_state_stats([2.5])
    assert one["epochs"] == 1 and one["first_s"] == 2.5
    # a 1-epoch run has no warm window: nulls, not fictitious zeros
    assert one["warm_median_s"] is None
    # the ratio of the two is no field any more: what the compiler took is
    # measured (compile_cache_info), not read off the first epoch
    assert set(one) == {"epochs", "first_s", "warm_median_s", "warm_mean_s",
                        "compile_overhead_s"}


def test_steady_state_stats_attribution_math():
    s = collectors.steady_state_stats([5.0, 1.0, 2.0, 3.0])
    assert s["epochs"] == 4 and s["first_s"] == 5.0
    assert s["warm_median_s"] == 2.0  # median of [1, 2, 3]
    assert s["warm_mean_s"] == pytest.approx(2.0)
    assert s["compile_overhead_s"] == pytest.approx(3.0)  # 5 - 2
    # ... an inference; beside it the snapshot of what was measured: 2.5 s
    # of the 3.0 were the compiler's, one program of two read from the cache
    reg = _registry_with(requests=2, cache_hits=1, cache_misses=1, trace_s=0.5,
                         lower_s=0.25, backend_s=1.75, retrieve_s=0.125)
    info = collectors.compile_cache_info(reg)
    assert info["trace_s"] + info["lower_s"] + info["backend_s"] == pytest.approx(2.5)
    assert (info["requests"], info["hits"], info["misses"]) == (2, 1, 1)
    assert info["retrieve_s"] == 0.125 <= info["backend_s"]
    # even warm count: midpoint interpolation
    s = collectors.steady_state_stats([4.0, 1.0, 3.0])
    assert s["warm_median_s"] == pytest.approx(2.0)


def test_steady_state_stats_clamps_negative_overhead():
    """A first epoch FASTER than warm (AOT/persistent-cache hit) must not
    report negative compile overhead."""
    s = collectors.steady_state_stats([1.0, 2.0, 2.0])
    assert s["compile_overhead_s"] == 0.0
    # a run that compiled nothing (every program already loaded) says so
    info = collectors.compile_cache_info(_registry_with())
    assert {info[k] for k in SNAPSHOT_KEYS} == {0.0}


# ---- compile_cache_info -----------------------------------------------------

SNAPSHOT_KEYS = ("requests", "hits", "misses", "trace_s", "lower_s", "backend_s",
                 "retrieve_s")


def _registry_with(**counters):
    """A registry whose ``compile.*`` counters stand as obs/compiles would
    have left them."""
    reg = registry.MetricsRegistry("t", algorithm="A", fingerprint="f")
    for key, value in counters.items():
        assert key in compiles.COUNTERS
        reg.counter_add("compile." + key, value)
    return reg


def test_compile_cache_info_reports_the_configured_dir(tmp_path):
    import jax

    info = collectors.compile_cache_info(_registry_with())
    assert set(info) == {"persistent_cache_dir", "enabled", *SNAPSHOT_KEYS}
    assert isinstance(info["enabled"], bool)

    before = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        on = collectors.compile_cache_info(_registry_with(requests=3))
        assert on["requests"] == 3
        assert on["enabled"] is True
        assert on["persistent_cache_dir"] == str(tmp_path)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


# ---- phase_snapshot ---------------------------------------------------------


def test_phase_snapshot_none_and_live_timers():
    assert collectors.phase_snapshot(None) == {}
    timers = PhaseTimers()
    with timers.phase("graph_load"):
        pass
    with timers.phase("graph_load"):
        pass
    snap = collectors.phase_snapshot(timers)
    assert snap["graph_load"]["count"] == 2
    assert snap["graph_load"]["total_s"] >= 0.0
