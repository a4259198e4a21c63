"""Bench harness checks (supervisor/worker split, graph cache).

The bench is the round's deliverable; its host-graph cache and worker JSON
contract get the same test discipline as the framework proper. The heavy
TPU paths are exercised by the driver; here the CPU platform validates the
machinery end to end at toy scale.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np

import bench
from neutronstarlite_tpu import native


def _canonical_csc(g):
    """(row_indices, weights) with each dst segment sorted by src — the
    native OpenMP builder orders tie edges nondeterministically ACROSS
    builds (CHANGES PR 2), so an equality check between two builds of the
    same edge list must compare per-segment multisets, not raw order."""
    dst_of = np.repeat(
        np.arange(g.v_num, dtype=np.int64), np.diff(g.column_offset)
    )
    order = np.lexsort((g.edge_weight_forward, g.row_indices, dst_of))
    return g.row_indices[order], g.edge_weight_forward[order]


def test_graph_cache_round_trip(tmp_path, monkeypatch):
    monkeypatch.setenv("NTS_BENCH_CACHE", str(tmp_path))
    d, v_num, e_num, gen_s = bench.build_and_cache_graph(0.0005)
    assert os.path.exists(os.path.join(d, "ok"))
    g, src, dst = bench.load_cached_graph(d)
    assert g.v_num == v_num and len(src) == len(dst)

    # must equal a direct build (the cache is a pure serialization).
    # Canonicalized per dst segment: the cached graph and the rebuild are
    # two separate native builds, whose tie-edge order is unspecified —
    # the graphs must agree as per-dst weighted neighbor MULTISETS
    # (raw-order equality was the env-flaky form of this test)
    from neutronstarlite_tpu.graph.storage import build_graph

    want = build_graph(src, dst, v_num, weight="gcn_norm")
    np.testing.assert_array_equal(g.column_offset, want.column_offset)
    g_src, g_w = _canonical_csc(g)
    w_src, w_w = _canonical_csc(want)
    np.testing.assert_array_equal(g_src, w_src)
    np.testing.assert_allclose(g_w, w_w)

    # second call is a cache hit: no rebuild
    d2, _, _, gen_s2 = bench.build_and_cache_graph(0.0005)
    assert d2 == d and gen_s2 == 0.0


def test_stale_cache_detected(tmp_path, monkeypatch):
    monkeypatch.setenv("NTS_BENCH_CACHE", str(tmp_path))
    d, v_num, e_num, _ = bench.build_and_cache_graph(0.0005)
    # simulate a generator/constant change leaving old bytes behind
    meta = json.load(open(os.path.join(d, "meta.json")))
    meta["v_num"] += 1
    json.dump(meta, open(os.path.join(d, "meta.json"), "w"))
    try:
        bench.load_cached_graph(d)
        raise AssertionError("stale cache not detected")
    except AssertionError as e:
        assert "stale graph cache" in str(e)


def test_worker_subprocess_contract(tmp_path, monkeypatch):
    """One worker run on CPU: must print a single parseable JSON line with
    epoch timings (the supervisor's whole interface to the measurement)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["NTS_BENCH_CACHE"] = str(tmp_path)
    env["PYTHONPATH"] = os.path.dirname(os.path.dirname(__file__))
    monkeypatch.setenv("NTS_BENCH_CACHE", str(tmp_path))
    d, _, _, _ = bench.build_and_cache_graph(0.0005)
    r = subprocess.run(
        [
            sys.executable, os.path.join(env["PYTHONPATH"], "bench.py"),
            "--worker", "--worker-config", "eager/ell/float32",
            "--epochs", "1", "--warmup", "1", "--cache-dir", d,
            "--kernel-tile", "0", "--platform", "cpu",
        ],
        capture_output=True, text=True, timeout=300, env=env,
    )
    assert r.returncode == 0, r.stderr[-1500:]
    info = json.loads(r.stdout.strip().splitlines()[-1])
    assert info["epoch_s"] > 0
    # every result names the device it was measured on, as JAX reported it
    assert info["device"]["platform"] == "cpu"
    assert info["device"]["device_kind"] and info["device"]["count"] >= 1
    assert len(info["epoch_times"]) == 2  # warmup + measured
    assert np.isfinite(info["loss"])
    # the obs run_summary record rides the worker JSON — the supervisor
    # attaches it under extra.metrics so BENCH_*.json carries attribution
    assert info["metrics"]["event"] == "run_summary"
    assert info["metrics"]["epochs"] == 2
    assert info["metrics"]["epoch_time"]["first_s"] > 0


def test_worker_refuses_another_platform(tmp_path):
    """A worker measures only on the platform it was asked for (default
    tpu): on the CPU rig it must exit non-zero BEFORE loading any graph,
    print no result line, and say what JAX reported."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.dirname(os.path.dirname(__file__))
    r = subprocess.run(
        [
            sys.executable, os.path.join(env["PYTHONPATH"], "bench.py"),
            "--worker", "--worker-config", "eager/ell/float32",
            "--cache-dir", str(tmp_path / "never_read"),
        ],
        capture_output=True, text=True, timeout=300, env=env,
    )
    assert r.returncode == 2, (r.returncode, r.stderr[-1500:])
    assert "'platform': 'cpu'" in r.stderr and "'tpu'" in r.stderr
    assert "epoch_s" not in r.stdout


def test_bench_matrix_measures_one_cfg():
    """The workload-matrix tool's per-cfg measurement contract. Runs the
    COMMITTED smoke cfg (fixtures-backed) — gcn_cora.cfg points at the
    /root/reference data checkout, which only some rigs carry, and this
    test's contract is the measurement plumbing, not the dataset."""
    from neutronstarlite_tpu.tools.bench_matrix import measure_cfg

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    row = measure_cfg(os.path.join(repo, "configs", "gcn_cora_smoke.cfg"),
                      epochs=1, warmup=1)
    assert row["algorithm"] == "GCNCPU"
    assert row["epoch_s"] > 0
    assert np.isfinite(row["loss"])


def test_run_nts_partitions_override(monkeypatch, tmp_path):
    """run_nts.sh parity: NTS_PARTITIONS_OVERRIDE (its <slots> argument)
    must override the cfg's PARTITIONS before dispatch."""
    from neutronstarlite_tpu.utils.config import InputInfo

    from neutronstarlite_tpu.run import apply_launcher_overrides

    cfg_path = tmp_path / "t.cfg"
    cfg_path.write_text("ALGORITHM:GCNCPU\nVERTICES:10\nPARTITIONS:2\n")
    monkeypatch.setenv("NTS_PARTITIONS_OVERRIDE", "7")
    cfg = apply_launcher_overrides(InputInfo.read_from_cfg_file(str(cfg_path)))
    assert cfg.partitions == 7
    monkeypatch.delenv("NTS_PARTITIONS_OVERRIDE")
    cfg = apply_launcher_overrides(InputInfo.read_from_cfg_file(str(cfg_path)))
    assert cfg.partitions == 2


def test_bench_sample_contract(tmp_path, monkeypatch, capsys):
    """Sampled-bench JSON contract at toy scale on CPU: one parseable line
    with a positive batch time and the workload descriptors."""
    monkeypatch.setenv("NTS_BENCH_CACHE", str(tmp_path))
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    from neutronstarlite_tpu.tools.bench_sample import main as sample_main

    rc = sample_main([
        "--scale", "0.001", "--batch-size", "32", "--fanout", "4-4",
        "--batches", "4", "--warmup", "1",
    ])
    assert rc == 0
    line = capsys.readouterr().out.strip().splitlines()[-1]
    rec = json.loads(line)
    assert rec["metric"] == "gcn_reddit_sampled_batch_time"
    assert rec["value"] > 0
    assert rec["extra"]["batches_per_epoch"] >= 1
    assert np.isfinite(rec["extra"]["final_loss"])


def test_worker_paths_agree(tmp_path, monkeypatch):
    """The pallas/blocked worker configs must run end-to-end and agree with
    the ELL path's loss bit-for-bit (same math, different layouts) — a
    plumbing bug here would otherwise burn an on-chip measurement slot.

    The graph the workers load is built here by the NumPy builder (and
    NTS_NO_NATIVE keeps the workers on it): the native OpenMP builder
    orders a vertex's edges differently on every build, and the ELL levels
    and the block-sparse kernel sum those edges in different orders, so
    for some orders the losses differ in the last bit (2 of 25 random
    orders of this graph; the NumPy builder's stable order is not one) —
    for reasons that have nothing to do with the layout plumbing under test.
    For the same reason the workers' CPU backend runs single-threaded: on a
    loaded machine (the suite's other workers) its thread pool splits a sum
    differently from one process to the next, and the three losses then
    differ in the last bit, whichever path falls out (seen in PR 38's runs,
    and reproduced beside eight busy processes)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = env.get("XLA_FLAGS", "") + " --xla_cpu_multi_thread_eigen=false"
    env["JAX_PLATFORMS"] = "cpu"
    env["NTS_BENCH_CACHE"] = str(tmp_path)
    env["NTS_NO_NATIVE"] = "1"
    env["PYTHONPATH"] = os.path.dirname(os.path.dirname(__file__))
    monkeypatch.setenv("NTS_BENCH_CACHE", str(tmp_path))
    monkeypatch.setattr(native, "available", lambda: False)
    d, _, _, _ = bench.build_and_cache_graph(0.0005)
    losses = {}
    for path, tile in (("ell", 0), ("pallas", 0), ("blocked", 64)):
        r = subprocess.run(
            [
                sys.executable, os.path.join(env["PYTHONPATH"], "bench.py"),
                "--worker", "--worker-config", f"eager/{path}/float32",
                "--epochs", "1", "--warmup", "1", "--cache-dir", d,
                "--kernel-tile", str(tile), "--platform", "cpu",
            ],
            capture_output=True, text=True, timeout=300, env=env,
        )
        assert r.returncode == 0, (path, r.stderr[-1500:])
        losses[path] = json.loads(r.stdout.strip().splitlines()[-1])["loss"]
    assert losses["pallas"] == losses["ell"], losses
    assert losses["blocked"] == losses["ell"], losses


def test_sweep_hang_fences(tmp_path, monkeypatch, capsys):
    """Round-3 postmortem regression: a path whose compile hangs (leg ends
    in TIMEOUT) must (a) be capped at the per-leg budget, not the whole
    sweep budget, and (b) forfeit its remaining sweep legs — so the later
    paths still get measured and the sweep still finds a winner."""
    calls = []

    def fake_worker(order, path, precision, epochs, warmup, cache_dir,
                    kernel_tile, timeout_s, platform):
        calls.append((order, path, round(timeout_s)))
        if path == "pallas":
            return {"error": f"TIMEOUT after {timeout_s:.0f}s", "wall_s": 1.0}
        ep = {"ell": 2.0, "scatter": 5.0}[path]
        return {"epoch_s": ep, "loss": 0.5, "device": "fake", "wall_s": 1.0}

    monkeypatch.delenv("NTS_SWEEP_LEG_CAP_S", raising=False)
    monkeypatch.setattr(bench, "start_watchdog", lambda *a: None)
    monkeypatch.setattr(bench, "run_worker_config", fake_worker)
    monkeypatch.setattr(
        bench, "build_and_cache_graph",
        lambda scale: (str(tmp_path), 1000, 5000, 0.1),
    )
    rc = bench.main(["--deadline", "1000", "--epochs", "1", "--warmup", "0"])
    # the winner was measured and is printed, but a leg that timed out is a
    # failure of the run: it shows in extra.sweep AND in the exit code
    assert rc == 4
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    # the winner is the fastest NON-hung path, measured at the leg cap
    assert rec["extra"]["path"] == "ell"
    # round 4: the expected winner (ell) sweeps FIRST; pallas follows.
    # The hung pallas leg is capped well below the sweep budget: the leg
    # cap is deadline*0.15 with the 3x table-build multiplier (pallas =
    # bsp tables now), itself clamped to 35% of the sweep budget
    assert calls[0][:2] == ("standard", "ell")
    first_pallas = next(c for c in calls if c[1] == "pallas")
    assert first_pallas[2] <= 228
    # eager/pallas never spawned a worker: the path was fenced after the
    # first TIMEOUT
    assert ("eager", "pallas") not in {c[:2] for c in calls}
    skipped = [
        r for r in rec["extra"]["sweep"]
        if r["path"] == "pallas" and "skipped" in str(r.get("error", ""))
    ]
    assert skipped, rec["extra"]["sweep"]


def test_failed_final_measurement_prints_no_number(tmp_path, monkeypatch,
                                                   capsys):
    """A sweep timing is never substituted for a failed final measurement:
    the run exits non-zero and prints no result line."""

    def fake_worker(order, path, precision, epochs, warmup, cache_dir,
                    kernel_tile, timeout_s, platform):
        if epochs == 7:  # the final measurement
            return {"error": "worker rc=1", "wall_s": 1.0}
        return {"epoch_s": 2.0, "loss": 0.5, "device": "fake", "wall_s": 1.0}

    monkeypatch.setattr(bench, "start_watchdog", lambda *a: None)
    monkeypatch.setattr(bench, "run_worker_config", fake_worker)
    monkeypatch.setattr(
        bench, "build_and_cache_graph",
        lambda scale: (str(tmp_path), 1000, 5000, 0.1),
    )
    rc = bench.main(["--deadline", "1000", "--epochs", "7", "--warmup", "0"])
    assert rc == 1
    assert capsys.readouterr().out.strip() == ""
