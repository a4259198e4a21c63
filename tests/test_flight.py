"""obs/flight: the bounded ring, the trigger policy, and the e2e contract
— an injected fault must leave a schema-valid flight dump that
reconstructs the pre-fault epoch's spans through tools/trace_timeline.
"""

from __future__ import annotations

import glob
import json
import os

import pytest

from neutronstarlite_tpu.obs import flight, registry, schema
from neutronstarlite_tpu.resilience import faults

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _flight_env(monkeypatch, tmp_path):
    monkeypatch.setenv("NTS_FLIGHT_DIR", str(tmp_path / "flight"))
    monkeypatch.delenv("NTS_FLIGHT", raising=False)
    monkeypatch.delenv("NTS_FLIGHT_SPANS", raising=False)
    yield


def load_dump(path):
    events = [json.loads(l) for l in open(path) if l.strip()]
    assert schema.validate_stream(events) == len(events)
    return events


# ---- ring + trigger policy -------------------------------------------------


def test_ring_is_bounded_and_dump_is_oldest_first(tmp_path):
    rec = flight.FlightRecorder(capacity=64)
    reg = registry.MetricsRegistry("run-f", algorithm="A", fingerprint="f")
    reg.flight = rec
    for i in range(200):
        reg.event("epoch", epoch=i, seconds=0.1, loss=1.0)
    assert len(rec._ring) == 64
    path = rec.dump("manual")
    events = load_dump(path)
    assert len(events) == 64
    epochs = [e["epoch"] for e in events if e["event"] == "epoch"]
    assert epochs == sorted(epochs) and epochs[-1] == 199


def test_fault_rank_loss_giveup_and_breach_trigger(tmp_path):
    reg = registry.MetricsRegistry("run-t", algorithm="A", fingerprint="f")
    assert reg.flight is not None  # always-on by default
    reg.event("epoch", epoch=0, seconds=0.1, loss=1.0)
    reg.event("fault", kind="nonfinite_loss", epoch=1, injected=True)
    reg.event("rank_loss", partition=2, reason="heartbeat_miss")
    reg.event("recovery", action="rollback", epoch=1)  # NOT a trigger
    reg.event("recovery", action="giveup", epoch=1)
    reg.event(
        "slo_status", objective="serve_p99_ms<=50@5s",
        metric="serve_p99_ms", state="ok", threshold=50.0, window_s=5.0,
        value=1.0, burn_rate=0.0,
    )  # ok verdict: NOT a trigger
    reg.event(
        "slo_status", objective="serve_p99_ms<=50@5s",
        metric="serve_p99_ms", state="breach", threshold=50.0,
        window_s=5.0, value=200.0, burn_rate=9.0,
    )
    dumps = reg.flight.dumps
    assert len(dumps) == 4
    names = [os.path.basename(p) for p in dumps]
    assert any("fault_nonfinite_loss" in n for n in names)
    assert any("rank_loss" in n for n in names)
    assert any("giveup" in n for n in names)
    assert any("slo_breach_serve_p99_ms" in n for n in names)
    for p in dumps:
        load_dump(p)


def test_dump_cap_bounds_disk(monkeypatch):
    monkeypatch.setenv("NTS_FLIGHT_MAX_DUMPS", "2")
    reg = registry.MetricsRegistry("run-c", algorithm="A", fingerprint="f")
    for i in range(5):
        reg.event("fault", kind="nonfinite_loss", epoch=i)
    assert len(reg.flight.dumps) == 2
    assert reg.flight.dropped_triggers == 3


def test_flight_disabled_by_env(monkeypatch):
    monkeypatch.setenv("NTS_FLIGHT", "0")
    reg = registry.MetricsRegistry("run-d", algorithm="A", fingerprint="f")
    assert reg.flight is None
    reg.event("fault", kind="nonfinite_loss", epoch=0)  # no crash, no dump
    assert not glob.glob(
        os.path.join(os.environ["NTS_FLIGHT_DIR"], "*.jsonl")
    )


def test_default_dir_is_flight_subdir_of_metrics_dir(monkeypatch, tmp_path):
    """Dump records duplicate stream records; the default target is a
    SUBdirectory so metrics-dir *.jsonl globs never double-count."""
    monkeypatch.delenv("NTS_FLIGHT_DIR", raising=False)
    monkeypatch.setenv("NTS_METRICS_DIR", str(tmp_path / "m"))
    rec = flight.FlightRecorder(capacity=16)
    rec.record({"event": "epoch"})
    path = rec.dump("manual")
    assert os.path.dirname(path) == str(tmp_path / "m" / "flight")
    monkeypatch.delenv("NTS_METRICS_DIR", raising=False)
    rec2 = flight.FlightRecorder(capacity=16)
    assert rec2.dump("manual") is None  # nowhere to write: skip, loudly


@pytest.mark.skipif(not hasattr(__import__("signal"), "SIGUSR2"),
                    reason="no SIGUSR2 on this platform")
def test_sigusr2_snapshots_the_live_ring():
    import signal

    reg = registry.MetricsRegistry("run-s", algorithm="A", fingerprint="f")
    reg.event("epoch", epoch=0, seconds=0.1, loss=1.0)
    before = list(reg.flight.dumps)
    os.kill(os.getpid(), signal.SIGUSR2)
    assert len(reg.flight.dumps) == len(before) + 1
    events = load_dump(reg.flight.dumps[-1])
    assert any(e["event"] == "epoch" for e in events)


def test_recent_records_outlive_the_registry_that_wrote_them(monkeypatch):
    """The in-process reader (the benchmark's per-layer readers come after
    the trainer is gone): the newest ring is held by the module, filtered
    by event kind, oldest first; NTS_FLIGHT=0 leaves nothing to read."""
    import gc

    reg = registry.MetricsRegistry("run-r", algorithm="A", fingerprint="f")
    for i in range(3):
        reg.event("span", name="epoch", cat="epoch", span_id=f"s{i}",
                  trace_id="t", parent_id=None, t0=float(i), dur_s=0.5)
        reg.event("epoch", epoch=i, seconds=0.5, loss=1.0)
    del reg
    gc.collect()
    spans = flight.recent_records("span")
    assert [s["span_id"] for s in spans] == ["s0", "s1", "s2"]
    assert [e["epoch"] for e in flight.recent_records("epoch")] == [0, 1, 2]
    assert flight.recent_records("no_such_kind") == []
    # a newer registry takes over; with the ring off there is none to read
    registry.MetricsRegistry("run-n", algorithm="A", fingerprint="f")
    assert flight.recent_records("span") == []
    flight.set_active(None)
    assert flight.recent_records("span") is None


# ---- e2e: injected fault -> dump -> timeline reconstruction ----------------


def test_injected_fault_dump_reconstructs_prefault_epoch(
    tmp_path, monkeypatch, capsys
):
    """The acceptance path: nan_loss injected at epoch 2 under the
    supervisor -> the guard trips -> the fault record triggers a dump
    whose ring holds the PRECEDING epoch's spans at full resolution, and
    tools/trace_timeline renders it natively."""
    from neutronstarlite_tpu.models.gcn import GCNTrainer
    from neutronstarlite_tpu.resilience.supervisor import supervised_run
    from tests.test_models import _planted_cfg, _planted_data

    monkeypatch.setenv("NTS_FAULT_SPEC", "nan_loss@epoch=2")
    monkeypatch.setenv("NTS_BACKOFF_BASE_S", "0")
    faults.reset()
    try:
        src, dst, datum = _planted_data(seed=11)
        trainer = GCNTrainer.from_arrays(
            _planted_cfg(epochs=4), src, dst, datum
        )
        result = supervised_run(trainer)
        assert result["loss"] is not None  # the run survived the fault
    finally:
        faults.reset()

    dumps = sorted(glob.glob(
        os.path.join(os.environ["NTS_FLIGHT_DIR"], "flight_*.jsonl")
    ))
    assert dumps, "injected fault left no flight dump"
    events = load_dump(dumps[0])

    fault_recs = [e for e in events if e["event"] == "fault"]
    assert fault_recs and fault_recs[-1]["kind"] == "nonfinite_loss"
    assert fault_recs[-1]["epoch"] == 2
    # the pre-fault epoch's spans are in the ring at full resolution
    epoch_spans = {
        e.get("epoch") for e in events
        if e["event"] == "span" and e.get("name") == "epoch"
    }
    assert 1 in epoch_spans, (
        f"pre-fault epoch span missing from the dump (got {epoch_spans})"
    )
    # ...and the dump renders natively through the timeline CLI
    from neutronstarlite_tpu.tools.trace_timeline import main as tl_main

    assert tl_main([dumps[0]]) == 0
    out = capsys.readouterr().out
    assert "span timeline:" in out
    assert "epoch" in out
