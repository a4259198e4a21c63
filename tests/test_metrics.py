"""obs subsystem: registry unit behavior + the end-to-end JSONL smoke.

The smoke trains 2 epochs of tiny GCN (real Cora structure from the
committed fixture) on the CPU rig with NTS_METRICS_DIR set, validates the
emitted stream against the schema, and renders it through the
metrics_report CLI — the ISSUE 1 acceptance path, fast enough for tier-1.
"""

from __future__ import annotations

import glob
import json
import os

import pytest

from neutronstarlite_tpu.obs import registry, schema

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---- registry unit behavior -------------------------------------------------


def test_registry_accumulates_without_sink(monkeypatch):
    monkeypatch.delenv("NTS_METRICS_DIR", raising=False)
    reg = registry.open_run("GCNCPU", cfg={"a": 1}, seed=0)
    assert reg.path is None
    reg.counter_add("wire.bytes_fwd", 100)
    reg.counter_add("wire.bytes_fwd", 50)
    reg.gauge_set("wire.comm_layer", "ring")
    reg.observe("epoch", 0.25)
    reg.observe("epoch", 0.35)
    snap = reg.snapshot()
    assert snap["counters"]["wire.bytes_fwd"] == 150
    assert snap["gauges"]["wire.comm_layer"] == "ring"
    assert snap["timings"]["epoch"]["count"] == 2
    assert snap["timings"]["epoch"]["total_s"] == pytest.approx(0.6)
    rec = reg.run_summary(epochs=2)
    assert rec["event"] == "run_summary"
    assert rec["counters"]["wire.bytes_fwd"] == 150
    assert reg.summary is rec


def test_registry_writes_validated_jsonl(tmp_path, monkeypatch):
    monkeypatch.setenv("NTS_METRICS_DIR", str(tmp_path))
    reg = registry.open_run("GCNDIST", cfg={"a": 2}, seed=3)
    assert reg.path and os.path.dirname(reg.path) == str(tmp_path)
    reg.epoch_event(0, 0.5, loss=1.25)
    reg.epoch_event(1, 0.4, loss=1.10, wire_bytes_fwd=4096)
    reg.close()
    events = [
        json.loads(line) for line in open(reg.path) if line.strip()
    ]
    assert schema.validate_stream(events) == 3  # run_start + 2 epochs
    assert [e["seq"] for e in events] == [0, 1, 2]
    assert events[2]["wire_bytes_fwd"] == 4096


def test_stream_size_guard_rotates_with_loud_marker(tmp_path, monkeypatch):
    """NTS_METRICS_MAX_MB: the stream rotates instead of growing without
    bound; the fresh file opens with a schema-valid stream_rotated marker,
    seq stays monotonic across the rotation, and only ONE previous chunk
    is retained (bounded disk)."""
    monkeypatch.setenv("NTS_METRICS_MAX_MB", "0.002")  # ~2 KB
    reg = registry.MetricsRegistry(
        "run-rot", algorithm="GCN", fingerprint="f",
        path=str(tmp_path / "rot.jsonl"),
    )
    for i in range(60):
        reg.epoch_event(i, 0.1, loss=1.0)
    reg.close()
    assert reg.rotations >= 1
    assert (tmp_path / "rot.jsonl.1").exists()
    assert not (tmp_path / "rot.jsonl.2").exists()
    # both the live file and the retained chunk stay schema-valid; the
    # live file leads with the loud marker
    live = [json.loads(l) for l in open(tmp_path / "rot.jsonl")]
    old = [json.loads(l) for l in open(tmp_path / "rot.jsonl.1")]
    assert schema.validate_stream(live) == len(live)
    assert schema.validate_stream(old) == len(old)
    assert live[0]["event"] == "stream_rotated"
    assert "NTS_METRICS_MAX_MB" in live[0]["reason"]
    seqs = [e["seq"] for e in old + live]
    assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
    # the cap actually bounds the live file (marker + tail, not 60 epochs)
    assert os.path.getsize(tmp_path / "rot.jsonl") <= 4096


def test_stream_size_guard_off_by_default(tmp_path, monkeypatch):
    monkeypatch.delenv("NTS_METRICS_MAX_MB", raising=False)
    reg = registry.MetricsRegistry(
        "run-nr", algorithm="GCN", fingerprint="f",
        path=str(tmp_path / "nr.jsonl"),
    )
    for i in range(200):
        reg.epoch_event(i, 0.1)
    reg.close()
    assert reg.rotations == 0
    assert not (tmp_path / "nr.jsonl.1").exists()


def test_config_fingerprint_stable_and_sensitive():
    from neutronstarlite_tpu.utils.config import InputInfo

    a, b = InputInfo(), InputInfo()
    assert registry.config_fingerprint(a) == registry.config_fingerprint(b)
    assert len(registry.config_fingerprint(a)) == 12
    b.epochs += 1
    assert registry.config_fingerprint(a) != registry.config_fingerprint(b)


def test_schema_rejects_bad_records():
    good = {"event": "epoch", "run_id": "r", "schema": schema.SCHEMA_VERSION,
            "ts": 1.0, "seq": 0, "epoch": 0, "seconds": 0.5, "loss": None}
    schema.validate_event(good)
    for mutate in (
        {"schema": 999},
        {"seconds": 0.0},
        {"epoch": -1},
        {"loss": "high"},
    ):
        bad = dict(good, **mutate)
        with pytest.raises(ValueError):
            schema.validate_event(bad)
    with pytest.raises(ValueError):
        schema.validate_event({"event": "epoch"})  # missing envelope


# ---- end-to-end smoke (ISSUE 1 acceptance) ---------------------------------


@pytest.fixture(scope="module")
def smoke_metrics_dir(tmp_path_factory):
    d = tmp_path_factory.mktemp("metrics")
    env_before = os.environ.get("NTS_METRICS_DIR")
    os.environ["NTS_METRICS_DIR"] = str(d)
    try:
        from neutronstarlite_tpu.run import main as run_main

        rc = run_main([os.path.join(REPO, "configs", "gcn_cora_smoke.cfg")])
    finally:
        if env_before is None:
            os.environ.pop("NTS_METRICS_DIR", None)
        else:
            os.environ["NTS_METRICS_DIR"] = env_before
    assert rc == 0
    return d


def test_run_emits_schema_valid_stream(smoke_metrics_dir):
    files = sorted(glob.glob(os.path.join(str(smoke_metrics_dir), "*.jsonl")))
    assert files, "no JSONL stream written under NTS_METRICS_DIR"
    events = [
        json.loads(line)
        for f in files
        for line in open(f)
        if line.strip()
    ]
    assert schema.validate_stream(events) == len(events)
    kinds = [e["event"] for e in events]
    assert kinds.count("epoch") == 2
    assert kinds.count("run_summary") == 1

    summ = [e for e in events if e["event"] == "run_summary"][-1]
    assert summ["epochs"] == 2
    et = summ["epoch_time"]
    assert et["first_s"] > 0 and et["warm_median_s"] > 0
    assert et["compile_overhead_s"] >= 0
    # phase buckets from init_graph/init_nn ride the summary
    assert "graph_load" in summ["phases"] and "datum_load" in summ["phases"]
    # memory: explicit nulls on the CPU rig (available=false), real stats
    # on a backend exposing memory_stats — both schema-valid
    assert isinstance(summ["memory"]["available"], bool)
    if not summ["memory"]["available"]:
        assert summ["memory"]["peak_bytes_in_use"] is None
    assert summ["result"]["acc"]["train"] is not None
    # the device as JAX reported it (conftest: the 8-virtual-device CPU rig)
    assert summ["device"] == {
        "platform": "cpu", "device_kind": "cpu", "count": 8,
    }


def test_metrics_report_renders_reference_shape(smoke_metrics_dir, capsys):
    from neutronstarlite_tpu.tools.metrics_report import main as report_main

    rc = report_main([str(smoke_metrics_dir)])
    out = capsys.readouterr().out
    assert rc == 0
    assert "--------------------finish algorithm !" in out
    assert "#avg_epoch_time=" in out and "(ms)" in out
    assert "#warm_median_epoch_time=" in out
    assert "#compile_overhead=" in out
    assert "#graph_load_time=" in out


def test_metrics_report_synthesizes_from_epochs_and_compares(tmp_path, capsys):
    """A stream whose run died before run_summary still renders, and two
    runs produce the cross-run comparison table."""
    def write_stream(name, run_id, n_epochs, with_summary):
        reg = registry.MetricsRegistry(
            run_id, algorithm="GCNDIST", fingerprint="deadbeef0123",
            path=str(tmp_path / name),
        )
        reg.event("run_start", algorithm="GCNDIST",
                  fingerprint="deadbeef0123")
        for i in range(n_epochs):
            reg.epoch_event(i, 0.1 + 0.01 * i, loss=2.0 - 0.1 * i)
        if with_summary:
            from neutronstarlite_tpu.obs.collectors import steady_state_stats

            reg.counter_add("wire.bytes_fwd", 1 << 20)
            reg.run_summary(
                epochs=n_epochs,
                epoch_time=steady_state_stats([0.1, 0.11, 0.12]),
                avg_epoch_s=0.11,
                phases={},
                memory={"available": False, "bytes_in_use": None,
                        "peak_bytes_in_use": None, "devices": []},
            )
        reg.close()

    write_stream("a.jsonl", "run-a", 3, with_summary=True)
    write_stream("b.jsonl", "run-b", 3, with_summary=False)
    from neutronstarlite_tpu.tools.metrics_report import main as report_main

    rc = report_main([str(tmp_path)])
    out = capsys.readouterr().out
    assert rc == 0
    assert "(synthesized)" in out          # run-b had no run_summary
    assert "run-a" in out and "run-b" in out
    assert "warm_ms" in out                # comparison table header


def test_metrics_report_fails_on_empty(tmp_path):
    from neutronstarlite_tpu.tools.metrics_report import main as report_main

    empty = tmp_path / "empty.jsonl"
    empty.write_text("")
    assert report_main([str(empty)]) == 1


def test_metrics_report_renders_recovery_timeline(tmp_path, capsys):
    """fault/recovery records (resilience/) render as an offset-stamped
    recovery timeline under the run's #key=value block."""
    from neutronstarlite_tpu.tools import metrics_report

    reg = registry.MetricsRegistry(
        "run-tl", algorithm="GCN", fingerprint="f",
        path=str(tmp_path / "tl.jsonl"),
    )
    reg.event("run_start", algorithm="GCN", fingerprint="f")
    reg.epoch_event(0, 0.5, loss=1.0)
    reg.event("fault", kind="nonfinite_loss", epoch=1, attempt=1)
    reg.event("recovery", action="rollback", epoch=1, attempt=1)
    reg.epoch_event(1, 0.4, loss=0.9)
    reg.close()

    assert metrics_report.main([str(tmp_path / "tl.jsonl")]) == 0
    out = capsys.readouterr().out
    assert "recovery timeline:" in out
    assert "fault" in out and "kind=nonfinite_loss" in out
    assert "recovery" in out and "action=rollback" in out


def _write_flight_dump(flight_dir, name="flight_x-fault.jsonl"):
    """A minimal schema-valid flight dump (what obs/flight snapshots)."""
    flight_dir.mkdir(parents=True, exist_ok=True)
    reg = registry.MetricsRegistry(
        "run-fl", algorithm="GCN", fingerprint="f",
        path=str(flight_dir / name),
    )
    reg.epoch_event(0, 0.5, loss=1.0)
    reg.event("fault", kind="nonfinite_loss", epoch=1, injected=True)
    reg.close()


def test_metrics_report_flight_only_dir_renders_dumps_with_hint(
    tmp_path, capsys
):
    """ISSUE 13 fix: a metrics dir whose ONLY contents are flight/ dumps
    used to exit 1 with a bare 'no .jsonl inputs found' — now the dumps
    render and stderr says what they are."""
    from neutronstarlite_tpu.tools import metrics_report

    _write_flight_dump(tmp_path / "flight")
    rc = metrics_report.main([str(tmp_path)])
    captured = capsys.readouterr()
    assert rc == 0
    assert "flight-recorder dump" in captured.err
    assert "rendering the dumps" in captured.err
    # the dump rendered as an ordinary (synthesized) stream
    assert "finish algorithm !" in captured.out
    assert "kind=nonfinite_loss" in captured.out


def test_metrics_report_never_double_counts_stream_plus_dump(
    tmp_path, capsys
):
    """A dir carrying BOTH a stream and flight dumps renders only the
    stream (dump records duplicate stream records) and notes the dumps
    exist."""
    from neutronstarlite_tpu.tools import metrics_report

    reg = registry.MetricsRegistry(
        "run-main", algorithm="GCN", fingerprint="f",
        path=str(tmp_path / "s.jsonl"),
    )
    reg.epoch_event(0, 0.5, loss=1.0)
    reg.epoch_event(1, 0.4, loss=0.9)
    reg.close()
    _write_flight_dump(tmp_path / "flight")

    rc = metrics_report.main([str(tmp_path)])
    captured = capsys.readouterr()
    assert rc == 0
    # exactly ONE run block: the stream; the dump did not double-render
    assert captured.out.count("finish algorithm !") == 1
    assert "run-main" in captured.out and "run-fl" not in captured.out
    assert "NOT included" in captured.err
    # the dumps are still reachable by passing flight/ explicitly
    rc = metrics_report.main([str(tmp_path / "flight")])
    out2 = capsys.readouterr().out
    assert rc == 0 and "run-fl" in out2
