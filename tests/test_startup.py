"""Process start-up (utils/platform.py): where the compile cache lives, and
chip_smoke.py's refusal to run on anything but the chip."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import jax

from neutronstarlite_tpu.utils import platform as nts_platform

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _recorded_updates(monkeypatch):
    """Replace jax.config.update with a recorder: the test process's own
    configuration (conftest: cache off) stays as it is."""
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
