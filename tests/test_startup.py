"""Process start-up (utils/platform.py): where the compile cache lives, and
chip_smoke.py's refusal to run on anything but the chip."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import jax
import pytest

from neutronstarlite_tpu.utils import platform as nts_platform

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _recorded_updates(monkeypatch):
    """Replace jax.config.update with a recorder: the test process's own
    configuration (conftest: the suite's cache) stays as it is."""
    updates = {}
    monkeypatch.setattr(
        jax.config, "update", lambda name, value: updates.__setitem__(name, value)
    )
    return updates


def test_cache_dir_from_env_sets_no_directory_in_code(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    updates = _recorded_updates(monkeypatch)
    nts_platform.configure_compile_cache()
    assert "jax_compilation_cache_dir" not in updates
    # every compile is cached, the sub-second AOT programs included
    assert updates == {"jax_persistent_cache_min_compile_time_secs": 0.0}


def test_cache_dir_defaults_to_fixed_path_in_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    updates = _recorded_updates(monkeypatch)
    nts_platform.configure_compile_cache()
    assert updates["jax_compilation_cache_dir"] == os.path.join(REPO, ".jax_cache")
    # fixed: the directory is part of the cache key
    assert nts_platform.CHECKOUT_CACHE_DIR == os.path.join(REPO, ".jax_cache")


def test_start_runtime_names_the_device():
    # conftest: the 8-virtual-device CPU rig
    assert nts_platform.start_runtime() == {
        "platform": "cpu", "device_kind": "cpu", "count": 8,
    }


def test_entry_point_caches_where_the_env_says(tmp_path):
    """``run`` with JAX_COMPILATION_CACHE_DIR set: the compiled programs
    land there, and the checkout gets no .jax_cache of its own."""
    cache = tmp_path / "cache"
    had_checkout_cache = os.path.exists(os.path.join(REPO, ".jax_cache"))
    env = dict(os.environ)
    env.update(
        JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(cache),
        JAX_ENABLE_COMPILATION_CACHE="true", PYTHONPATH=REPO,
    )
    r = subprocess.run(
        [sys.executable, "-m", "neutronstarlite_tpu.run",
         os.path.join(REPO, "configs", "gcn_cora_smoke.cfg")],
        capture_output=True, text=True, timeout=300, env=env, cwd=str(tmp_path),
    )
    assert r.returncode == 0, r.stdout[-1500:] + r.stderr[-1500:]
    assert f"compile cache {cache}" in r.stdout
    assert any(files for _, _, files in os.walk(cache)), "nothing was cached"
    assert os.path.exists(os.path.join(REPO, ".jax_cache")) == had_checkout_cache


def test_chip_smoke_result_line_has_exactly_the_contract_keys():
    """The last stdout line is ``ok`` + the device facts and nothing else;
    the legs, versions and times ride the report line before it."""
    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.remove(REPO)
    summary = {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
        "versions": {"jax": "0.9.0"}, "legs": {"dataset": {"status": "passed"}},
        "wall_s": 1.0,
    }
    assert json.loads(chip_smoke.result_line(summary)) == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
    }
    assert "\n" not in chip_smoke.result_line(summary)


def test_chip_smoke_refuses_the_cpu(tmp_path):
    """Off the TPU chip_smoke.py says what JAX reported, exits non-zero and
    prints no result — whatever the environment asked for."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, timeout=300, env=env, cwd=str(tmp_path),
    )
    assert r.returncode == 2, (r.returncode, r.stderr[-1500:])
    assert "chip_smoke device: platform=cpu device_kind='cpu'" in r.stdout
    assert "JAX reports 'cpu'" in r.stderr
    assert not any(line.startswith("{") for line in r.stdout.splitlines())


# ---- the runtime's start as spans (cat ``startup``) -------------------------

PRELUDE_SCRIPT = """
import json, os, sys, time
{before_import}
import neutronstarlite_tpu
from neutronstarlite_tpu import obs
with open("/proc/self/stat") as fh:
    ticks = int(fh.read().rsplit(")", 1)[1].split()[19])
suspended = time.clock_gettime(time.CLOCK_BOOTTIME) - time.clock_gettime(time.CLOCK_MONOTONIC)
reg = obs.open_run("T")
obs.Tracer(reg)
obs.Tracer(reg)  # a second tracer: the span is the first one's alone
obs.Tracer(obs.open_run("U"))
spans = [r for r in reg.flight.records("span")]
print(json.dumps({{"spans": spans, "proc_start": ticks / os.sysconf("SC_CLK_TCK") - suspended,
                  "t_import": neutronstarlite_tpu._T_IMPORT, "now": time.perf_counter()}}))
"""


@pytest.mark.parametrize("before_import, backend_live", [
    ("", 0),
    ("import jax; jax.devices()", 1),  # as benchmark/run.py's device gate does
])
def test_process_prelude_runs_from_the_kernels_start_to_the_packages_import(
        before_import, backend_live):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    r = subprocess.run(
        [sys.executable, "-c", PRELUDE_SCRIPT.format(before_import=before_import)],
        capture_output=True, text=True, timeout=120, env=env,
    )
    assert r.returncode == 0, r.stderr[-1500:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    (span,) = out["spans"]  # once a process, whatever the number of tracers
    assert (span["name"], span["cat"], span["parent_id"]) == ("process_prelude", "startup", None)
    assert span["backend_live"] == backend_live
    assert abs(span["t0"] - out["proc_start"]) <= 0.02
    assert span["t0"] + span["dur_s"] == pytest.approx(out["t_import"], abs=1e-9)
    # on the clock of every other span: it ended before the script's last
    # line, and an interpreter does not take a minute to reach its first
    assert 0.0 < span["dur_s"] < 60.0 and span["t0"] + span["dur_s"] < out["now"]
    if backend_live:  # the prelude holds JAX's import and the backend's start
        assert span["dur_s"] > 0.2


def test_start_runtime_times_the_backend_under_the_newest_tracer():
    from neutronstarlite_tpu import obs

    reg = obs.open_run("T")
    tracer = obs.Tracer(reg)
    with tracer.span("run", cat="lifecycle") as root:
        facts = nts_platform.start_runtime()
    # (the first tracer of a process also gets what waited for it)
    (span,) = [r for r in reg.flight.records("span") if r["parent_id"] == root.span_id]
    assert (span["name"], span["cat"]) == ("backend_init", "startup")
    assert (span["platform"], span["count"]) == (facts["platform"], facts["count"])


def test_a_backend_named_before_any_tracer_waits_for_the_first(monkeypatch):
    from neutronstarlite_tpu import obs
    from neutronstarlite_tpu.obs import trace

    monkeypatch.setattr(trace, "_tracers", [])
    monkeypatch.setattr(trace, "_newest", None)
    monkeypatch.setattr(trace, "_deferred", [])
    facts = nts_platform.start_runtime()  # a tool: the device first, a toolkit later
    reg = obs.open_run("T")
    obs.Tracer(reg)
    (span,) = reg.flight.records("span")
    assert (span["name"], span["cat"], span["parent_id"]) == ("backend_init", "startup", None)
    assert (span["platform"], span["count"]) == ("cpu", facts["count"])


def test_process_start_leaves_out_the_time_the_host_was_suspended(monkeypatch):
    """``/proc`` counts from boot on a clock that runs through a suspend;
    ``perf_counter`` reads one that stops: after 100 s of suspend the
    process's start is 100 s earlier on the spans' clock."""
    import time

    awake = nts_platform.process_start()
    real = time.clock_gettime
    monkeypatch.setattr(
        time, "clock_gettime",
        lambda clock: real(clock) + (100.0 if clock == time.CLOCK_BOOTTIME else 0.0))
    assert nts_platform.process_start() == pytest.approx(awake - 100.0, abs=1e-3)


def test_process_start_is_none_where_proc_does_not_say(monkeypatch):
    assert nts_platform.process_start() is not None  # Linux: the tests' machine
    real_open = open

    def no_proc(path, *a, **k):
        if path == "/proc/self/stat":
            raise OSError("no /proc here")
        return real_open(path, *a, **k)

    monkeypatch.setattr("builtins.open", no_proc)
    assert nts_platform.process_start() is None
    from neutronstarlite_tpu.obs import trace

    monkeypatch.setattr(trace, "_deferred", [])
    nts_platform.note_package_import(0.0)  # and then the span is not emitted
    assert trace._deferred == []
