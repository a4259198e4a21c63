"""Cross-component span tracing (obs/trace + tools/trace_timeline): the
ISSUE 5 acceptance paths.

Pinned contracts:
- Tracer mechanics: thread-local parenting, retroactive completion,
  NTS_TRACE=0 kill switch, error attribution on exceptions;
- clock model: per-stream mono->wall recovery and cross-rank epoch-marker
  alignment snap a 5-second-skewed rank onto the reference timeline;
- the Chrome trace-event export is structurally valid (and the validator
  actually rejects garbage);
- ACCEPTANCE (ring): a 4-partition ring_blocked sim run emits a valid
  Chrome trace and a measured ring overlap-efficiency number, with every
  ring_step record joined to its epoch span;
- ACCEPTANCE (serve): a 50-request serve smoke yields a per-request
  critical-path breakdown whose stage sum matches the recorded request
  latency within tolerance;
- retry cost derivation from fault/recovery/epoch records;
- metrics_report --diff exits non-zero on regression past --tol.
"""

from __future__ import annotations

import glob
import json
import os
import threading

import numpy as np
import pytest

from neutronstarlite_tpu.obs import registry, schema
from neutronstarlite_tpu.obs.trace import Tracer
from neutronstarlite_tpu.tools import trace_timeline

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _events_of(reg_path):
    return [json.loads(l) for l in open(reg_path) if l.strip()]


# ---- tracer mechanics -------------------------------------------------------


def test_tracer_nests_by_thread_and_supports_retroactive_spans(tmp_path):
    reg = registry.MetricsRegistry("t", algorithm="A", fingerprint="f",
                                   path=str(tmp_path / "t.jsonl"))
    tr = Tracer(reg)
    with tr.span("outer", cat="phase") as outer:
        with tr.span("inner", cat="phase"):
            pass
        # a retroactive span parents under the innermost OPEN span
        tr.complete("retro", dur_s=0.1, epoch=7)
    # explicit parent handles win over the stack
    tr.complete("child_of_outer", dur_s=0.2, parent=outer)

    # spans from another thread must NOT parent under this thread's stack
    got = {}

    def other():
        with tr.span("elsewhere", cat="serve") as h:
            got["parent"] = h.parent_id

    t = threading.Thread(target=other)
    with tr.span("main_open"):
        t.start()
        t.join()
    assert got["parent"] is None

    reg.close()
    evs = _events_of(tmp_path / "t.jsonl")
    assert schema.validate_stream(evs) == len(evs)
    by = {e["name"]: e for e in evs}
    assert by["inner"]["parent_id"] == by["outer"]["span_id"]
    assert by["retro"]["parent_id"] == by["outer"]["span_id"]
    assert by["child_of_outer"]["parent_id"] == by["outer"]["span_id"]
    assert by["outer"]["parent_id"] is None
    assert by["retro"]["epoch"] == 7 and by["retro"]["dur_s"] == 0.1
    # ids are unique; every span carries the common trace id
    ids = [e["span_id"] for e in evs]
    assert len(set(ids)) == len(ids)
    assert {e["trace_id"] for e in evs} == {"t"}


def test_tracer_disabled_by_env_and_error_attribution(tmp_path, monkeypatch):
    monkeypatch.setenv("NTS_TRACE", "0")
    reg = registry.MetricsRegistry("t", algorithm="A", fingerprint="f",
                                   path=str(tmp_path / "off.jsonl"))
    tr = Tracer(reg)
    with tr.span("quiet"):
        pass
    tr.complete("also_quiet", dur_s=0.5)
    reg.close()
    assert not (tmp_path / "off.jsonl").exists()  # zero records written

    monkeypatch.delenv("NTS_TRACE", raising=False)
    reg2 = registry.MetricsRegistry("t2", algorithm="A", fingerprint="f",
                                    path=str(tmp_path / "on.jsonl"))
    tr2 = Tracer(reg2)
    with pytest.raises(RuntimeError):
        with tr2.span("doomed"):
            raise RuntimeError("boom")
    reg2.close()
    evs = _events_of(tmp_path / "on.jsonl")
    assert evs[0]["name"] == "doomed" and evs[0]["error"] == "RuntimeError"


# ---- clock model ------------------------------------------------------------


def _mk_stream(path, rank, wall0, mono0, epochs):
    """Synthetic per-rank stream: run_start + one epoch span per entry.
    ``wall0 - mono0`` is the process's mono->wall offset; a skewed host
    simply gets a different wall0."""
    events = [{
        "event": "run_start", "run_id": f"r{rank}", "schema":
        schema.SCHEMA_VERSION, "ts": wall0, "seq": 0, "algorithm": "A",
        "fingerprint": "f", "process_index": rank,
    }]
    for i, (t0, dur) in enumerate(epochs):
        end_mono = t0 + dur
        events.append({
            "event": "span", "run_id": f"r{rank}",
            "schema": schema.SCHEMA_VERSION,
            "ts": wall0 + (end_mono - mono0), "seq": i + 1,
            "name": "epoch", "cat": "epoch", "span_id": f"e{i}",
            "trace_id": f"r{rank}", "parent_id": None,
            "t0": t0, "dur_s": dur, "rank": rank, "epoch": i,
        })
    assert schema.validate_stream(events) == len(events)
    return trace_timeline.Stream(str(path), events)


def test_epoch_marker_alignment_snaps_skewed_rank(tmp_path):
    # rank 0: mono starts at 10, wall at 1000; rank 1: same true timeline
    # but its wall clock runs 5 s AHEAD (NTP skew)
    s0 = _mk_stream(tmp_path / "a-p0.jsonl", 0, wall0=1000.0, mono0=10.0,
                    epochs=[(10.0, 1.0), (11.0, 1.0)])
    s1 = _mk_stream(tmp_path / "b-p1.jsonl", 1, wall0=1005.0, mono0=100.0,
                    epochs=[(100.0, 1.0), (101.0, 1.0)])
    assert s0.rank == 0 and s1.rank == 1
    assert s0.offset == pytest.approx(1000.0 - 10.0)
    assert s1.offset == pytest.approx(1005.0 - 100.0)
    trace_timeline.align_streams([s0, s1])
    assert s0.align == 0.0
    assert s1.align == pytest.approx(-5.0)
    e0, e1 = s0.epoch_ends(), s1.epoch_ends()
    for e in (0, 1):
        assert e0[e] == pytest.approx(e1[e])
    # the chrome export places both ranks on the aligned timeline
    trace = trace_timeline.chrome_trace([s0, s1])
    assert trace_timeline.validate_chrome_trace(trace) == len(
        trace["traceEvents"]
    )
    xs = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    by_rank = {}
    for e in xs:
        if e["name"] == "epoch":
            by_rank.setdefault(e["pid"], []).append(e["ts"])
    assert by_rank[0] == pytest.approx(by_rank[1], abs=1.0)  # us


def _mk_drifting_stream(path, rank, wall0, mono0, epochs, drift_per_s):
    """Like _mk_stream, but the host's wall clock DRIFTS: every elapsed
    monotonic second adds ``drift_per_s`` of wall error (a bad oscillator,
    not just a constant NTP offset)."""
    events = [{
        "event": "run_start", "run_id": f"r{rank}", "schema":
        schema.SCHEMA_VERSION, "ts": wall0, "seq": 0, "algorithm": "A",
        "fingerprint": "f", "process_index": rank,
    }]
    for i, (t0, dur) in enumerate(epochs):
        end_mono = t0 + dur
        elapsed = end_mono - mono0
        events.append({
            "event": "span", "run_id": f"r{rank}",
            "schema": schema.SCHEMA_VERSION,
            "ts": wall0 + elapsed + drift_per_s * elapsed, "seq": i + 1,
            "name": "epoch", "cat": "epoch", "span_id": f"e{i}",
            "trace_id": f"r{rank}", "parent_id": None,
            "t0": t0, "dur_s": dur, "rank": rank, "epoch": i,
        })
    assert schema.validate_stream(events) == len(events)
    return trace_timeline.Stream(str(path), events)


def test_alignment_recovers_skew_under_clock_drift(tmp_path):
    """Injected skew + drift: rank 1's wall clock starts 5 s ahead AND
    gains 10 ms per monotonic second. The median offset/alignment
    estimators must recover the shared timeline to within half the total
    drift accumulated over the run (the bound of a median corrector —
    residuals are the per-epoch drift around the middle sample)."""
    epochs = [(100.0 + i, 0.8) for i in range(6)]
    s0 = _mk_stream(tmp_path / "a-p0.jsonl", 0, wall0=1000.0, mono0=100.0,
                    epochs=epochs)
    s1 = _mk_drifting_stream(tmp_path / "b-p1.jsonl", 1, wall0=1005.0,
                             mono0=100.0, epochs=epochs, drift_per_s=0.010)
    trace_timeline.align_streams([s0, s1])
    assert s0.align == 0.0
    total_drift = 0.010 * (epochs[-1][0] + epochs[-1][1] - 100.0)
    # skew recovered: the -5 s shift dominates, residual bounded by drift
    assert s1.align == pytest.approx(-5.0, abs=total_drift)
    e0, e1 = s0.epoch_ends(), s1.epoch_ends()
    for e in e0:
        assert e0[e] == pytest.approx(e1[e], abs=total_drift / 2 + 1e-9)
    assert s1.align_warning is None  # aligned streams carry no warning


def _mk_spans_no_epochs(path, rank, wall0, mono0):
    """A span-bearing stream with NO epoch markers (a serve surface, or
    a trainer that died before epoch 0 closed)."""
    events = [{
        "event": "span", "run_id": f"r{rank}", "schema":
        schema.SCHEMA_VERSION, "ts": wall0 + 0.5, "seq": 0,
        "name": "flush", "cat": "serve", "span_id": "f0",
        "trace_id": f"r{rank}", "parent_id": None,
        "t0": mono0, "dur_s": 0.5, "rank": rank, "epoch": None,
    }]
    assert schema.validate_stream(events) == len(events)
    return trace_timeline.Stream(str(path), events)


def test_alignment_warns_not_crashes_without_epoch_markers(
    tmp_path, capsys,
):
    """The satellite pin: a rank with no alignment markers is a WARNING
    and a kept-own-clock stream, never a crash — and the timeline still
    renders."""
    s0 = _mk_stream(tmp_path / "a-p0.jsonl", 0, wall0=1000.0, mono0=10.0,
                    epochs=[(10.0, 1.0), (11.0, 1.0)])
    s1 = _mk_spans_no_epochs(tmp_path / "b-p1.jsonl", 1, wall0=1005.0,
                             mono0=100.0)
    trace_timeline.align_streams([s0, s1])
    assert s1.align == 0.0  # kept on its own wall clock
    assert "no epoch markers" in (s1.align_warning or "")
    assert "no epoch markers" in capsys.readouterr().err
    trace = trace_timeline.chrome_trace([s0, s1])
    assert trace_timeline.validate_chrome_trace(trace) > 0

    # no stream anchored at all: every span-bearing stream warns
    s2 = _mk_spans_no_epochs(tmp_path / "c-p0.jsonl", 0, wall0=1.0,
                             mono0=0.0)
    s3 = _mk_spans_no_epochs(tmp_path / "d-p1.jsonl", 1, wall0=2.0,
                             mono0=0.0)
    trace_timeline.align_streams([s2, s3])
    assert all("no stream carries epoch spans" in (s.align_warning or "")
               for s in (s2, s3))

    # anchored but disjoint epochs: the non-anchor stream warns
    s4 = _mk_stream(tmp_path / "e-p0.jsonl", 0, wall0=1000.0, mono0=10.0,
                    epochs=[(10.0, 1.0)])
    s5 = trace_timeline.Stream(str(tmp_path / "f-p1.jsonl"), [
        dict(e, epoch=(e.get("epoch") or 0) + 7,
             span_id=f"x{i}") if e["event"] == "span" else e
        for i, e in enumerate(_mk_stream(
            tmp_path / "f-p1.jsonl", 1, wall0=1000.0, mono0=10.0,
            epochs=[(10.0, 1.0)],
        ).events)
    ])
    trace_timeline.align_streams([s4, s5])
    assert "shares no epochs with the anchor" in (s5.align_warning or "")
    assert s5.align == 0.0


def test_chrome_trace_validator_rejects_garbage():
    with pytest.raises(ValueError, match="traceEvents"):
        trace_timeline.validate_chrome_trace({"events": []})
    bad_ph = {"traceEvents": [
        {"ph": "Z", "name": "x", "pid": 0, "tid": 0, "ts": 0}
    ]}
    with pytest.raises(ValueError, match="ph"):
        trace_timeline.validate_chrome_trace(bad_ph)
    no_dur = {"traceEvents": [
        {"ph": "X", "name": "x", "pid": 0, "tid": 0, "ts": 0}
    ]}
    with pytest.raises(ValueError, match="dur"):
        trace_timeline.validate_chrome_trace(no_dur)


# ---- ACCEPTANCE: 4-partition ring_blocked sim -> chrome + overlap ----------


@pytest.fixture(scope="module")
def ring_trace_dir(tmp_path_factory):
    """A tiny 4-partition DIST_PATH:ring_blocked_sim run with tracing and
    the overlap probe on; shared by the ring acceptance tests."""
    from neutronstarlite_tpu.graph.dataset import GNNDatum
    from neutronstarlite_tpu.models.base import get_algorithm
    from neutronstarlite_tpu.utils.config import InputInfo

    d = tmp_path_factory.mktemp("ring_trace")
    rng = np.random.default_rng(7)
    V, E = 80, 520
    src = rng.integers(0, V, size=E, dtype=np.uint32)
    dst = rng.integers(0, V, size=E, dtype=np.uint32)
    datum = GNNDatum.random_generate(V, 6, 3, seed=3)
    cfg = InputInfo()
    cfg.algorithm = "GCNDIST"
    cfg.vertices = V
    cfg.layer_string = "6-8-3"
    cfg.epochs = 2
    cfg.learn_rate = 0.01
    cfg.weight_decay = 1e-4
    cfg.decay_epoch = -1
    cfg.drop_rate = 0.0
    cfg.partitions = 4
    cfg.dist_path = "ring_blocked_sim"
    cfg.kernel_tile = 16
    env = {"NTS_METRICS_DIR": str(d), "NTS_OVERLAP_PROBE": "1"}
    before = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    try:
        tr = get_algorithm("GCNDIST").from_arrays(cfg, src, dst, datum)
        result = tr.run()
    finally:
        for k, v in before.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    assert np.isfinite(result["loss"])
    return d


def test_ring_sim_run_emits_valid_chrome_trace_and_overlap(
    ring_trace_dir, tmp_path, capsys
):
    out = str(tmp_path / "ring_chrome.json")
    rc = trace_timeline.main([str(ring_trace_dir), "--chrome", out,
                              "--json"])
    assert rc == 0
    report = json.loads(capsys.readouterr().out.strip())

    # a measured overlap-efficiency number (sim rig: the probe says so)
    ring = report["ring_overlap"]
    assert ring is not None
    assert isinstance(ring["efficiency"], (int, float))
    assert 0.0 <= ring["efficiency"] <= 1.0
    assert ring["simulated"] is True
    assert ring["overlap_s"] > 0 and ring["compute_s"] > 0
    assert ring["exchange_s"] > 0

    # the exported chrome trace is schema-valid and carries the lifecycle
    trace = json.load(open(out))
    n = trace_timeline.validate_chrome_trace(trace)
    assert n == len(trace["traceEvents"]) > 0
    names = {e["name"] for e in trace["traceEvents"] if e["ph"] == "X"}
    assert {"run", "epoch", "ring_overlap_probe", "step_device"} <= names

    # every ring_step record joins to an epoch span that exists
    evs = [
        json.loads(l)
        for f in glob.glob(os.path.join(str(ring_trace_dir), "*.jsonl"))
        for l in open(f) if l.strip()
    ]
    assert schema.validate_stream(evs) == len(evs)
    span_ids = {e["span_id"] for e in evs if e["event"] == "span"}
    hops = [e for e in evs if e["event"] == "ring_step"]
    assert hops and all(h["epoch_span"] in span_ids for h in hops)
    epoch_of_span = {
        e["span_id"]: e.get("epoch") for e in evs if e["event"] == "span"
    }
    assert all(epoch_of_span[h["epoch_span"]] == h["epoch"] for h in hops)


def test_ring_report_renders_overlap_block(ring_trace_dir, capsys):
    from neutronstarlite_tpu.tools.metrics_report import main as report_main

    rc = report_main([str(ring_trace_dir)])
    out = capsys.readouterr().out
    assert rc == 0
    assert "span timeline:" in out
    assert "#ring_overlap_efficiency=" in out
    assert "sim rig" in out


# ---- ACCEPTANCE: 50-request serve critical path ----------------------------


@pytest.fixture(scope="module")
def serve_trace_dir(tmp_path_factory):
    """Train a tiny sampled GCN, serve 50 requests with tracing on; the
    whole lifecycle (train + serve) lands in one per-process stream."""
    from neutronstarlite_tpu.models.gcn_sample import GCNSampleTrainer
    from neutronstarlite_tpu.serve.batcher import ServeOptions
    from neutronstarlite_tpu.serve.engine import InferenceEngine
    from neutronstarlite_tpu.serve.server import InferenceServer
    from tests.test_models import _planted_data

    d = tmp_path_factory.mktemp("serve_trace")
    env = {"NTS_METRICS_DIR": str(d), "NTS_SAMPLE_WORKERS": "0"}
    before = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    try:
        from neutronstarlite_tpu.utils.config import InputInfo

        cfg = InputInfo()
        cfg.algorithm = "GCNSAMPLESINGLE"
        cfg.vertices = 300
        cfg.layer_string = "16-24-4"
        cfg.fanout_string = "3-3"
        cfg.batch_size = 16
        cfg.epochs = 2
        cfg.learn_rate = 0.01
        cfg.weight_decay = 1e-4
        cfg.decay_epoch = -1
        cfg.drop_rate = 0.3
        cfg.checkpoint_dir = str(tmp_path_factory.mktemp("serve_ckpt"))
        src, dst, datum = _planted_data(v_num=300, seed=11)
        toolkit = GCNSampleTrainer.from_arrays(cfg, src, dst, datum)
        toolkit.run()

        opts = ServeOptions(max_batch=8, max_wait_ms=2, max_queue=256)
        engine = InferenceEngine(
            toolkit, cfg.checkpoint_dir, options=opts,
            rng=np.random.default_rng(5),
        )
        server = InferenceServer(engine)
        rng = np.random.default_rng(6)
        pending = [
            server.submit(rng.integers(0, 300, size=1)) for _ in range(50)
        ]
        for r in pending:
            r.result(timeout=120.0)
        stats = server.close()
        assert stats["requests"] == 50 and stats["shed"] == 0
    finally:
        for k, v in before.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    return d


def test_serve_critical_path_sums_to_recorded_latency(serve_trace_dir):
    evs = [
        json.loads(l)
        for f in glob.glob(os.path.join(str(serve_trace_dir), "*.jsonl"))
        for l in open(f) if l.strip()
    ]
    assert schema.validate_stream(evs) == len(evs)
    serve = trace_timeline.serve_critical_path(evs)
    assert serve is not None
    assert serve["n"] == 50  # every answered request has a breakdown
    for r in serve["requests"]:
        assert set(r["stages_ms"]) == set(trace_timeline.SERVE_STAGES)
        # the critical-path contract: the stage sum reproduces the
        # recorded end-to-end latency. The only unattributed gaps are
        # the flush-call handoff and the tail of the reply loop after
        # this request completed — microseconds of host work, bounded
        # generously for CI scheduling noise.
        assert abs(r["mismatch_ms"]) <= max(
            75.0, 0.5 * r["total_ms"]
        ), f"stage sum diverges from latency: {r}"
    assert serve["max_abs_mismatch_ms"] <= 75.0
    # medians exist for every stage and the queue is a real component
    p50 = serve["stage_p50_ms"]
    assert all(p50[s] is not None for s in trace_timeline.SERVE_STAGES)
    assert p50["queue"] >= 0.0


def test_serve_report_renders_critical_path(serve_trace_dir, capsys):
    from neutronstarlite_tpu.tools.metrics_report import main as report_main

    rc = report_main([str(serve_trace_dir)])
    out = capsys.readouterr().out
    assert rc == 0
    assert "#serve_critical_path_p50=" in out
    assert "critical=" in out


def test_serve_chrome_trace_spans_carry_request_joins(
    serve_trace_dir, tmp_path
):
    out = str(tmp_path / "serve_chrome.json")
    rc = trace_timeline.main([str(serve_trace_dir), "--chrome", out])
    assert rc == 0
    trace = json.load(open(out))
    trace_timeline.validate_chrome_trace(trace)
    reqs = [
        e for e in trace["traceEvents"]
        if e["ph"] == "X" and e["name"] == "request"
    ]
    assert len(reqs) == 50
    assert all("req_id" in e["args"] for e in reqs)
    # batcher-thread spans land on their own named track
    threads = {
        e["args"]["name"] for e in trace["traceEvents"]
        if e["ph"] == "M" and e["name"] == "thread_name"
    }
    assert any("serve-batcher" in t for t in threads)


# ---- retry cost -------------------------------------------------------------


def test_retry_report_measures_time_to_recover(tmp_path):
    reg = registry.MetricsRegistry("r", algorithm="A", fingerprint="f",
                                   path=str(tmp_path / "r.jsonl"))
    reg.event("epoch", epoch=0, seconds=1.0, loss=1.0)
    f = reg.event("fault", kind="nonfinite_loss", epoch=1, attempt=1)
    reg.event("recovery", action="rollback", epoch=1, attempt=1)
    e = reg.event("epoch", epoch=1, seconds=1.0, loss=0.9)
    reg.event(
        "run_summary", algorithm="A", fingerprint="f",
        counters={"resilience.replayed_epochs": 1}, gauges={}, timings={},
        epochs=2,
        epoch_time={"first_s": 1.0, "warm_median_s": 1.0,
                    "compile_overhead_s": 0.0},
        phases={}, memory={"available": False, "bytes_in_use": None,
                           "peak_bytes_in_use": None, "devices": []},
    )
    reg.close()
    evs = _events_of(tmp_path / "r.jsonl")
    retry = trace_timeline.retry_report(evs)
    assert retry["n"] == 1 and retry["replayed_epochs"] == 1
    ep = retry["episodes"][0]
    assert ep["kind"] == "nonfinite_loss" and ep["action"] == "rollback"
    assert ep["recover_s"] == pytest.approx(e["ts"] - f["ts"], abs=1e-6)
    assert retry["mean_recover_s"] == pytest.approx(ep["recover_s"])


# ---- metrics_report --diff --------------------------------------------------


def _write_summary_stream(path, run_id, warm_s, wire_bytes):
    from neutronstarlite_tpu.obs.collectors import steady_state_stats

    reg = registry.MetricsRegistry(run_id, algorithm="GCNDIST",
                                   fingerprint="f", path=str(path))
    reg.event("run_start", algorithm="GCNDIST", fingerprint="f")
    times = [warm_s * 3, warm_s, warm_s]
    for i, t in enumerate(times):
        reg.epoch_event(i, t, loss=1.0)
    reg.counter_add("wire.bytes_fwd", wire_bytes)
    reg.run_summary(
        epochs=3, epoch_time=steady_state_stats(times), avg_epoch_s=warm_s,
        phases={}, memory={"available": False, "bytes_in_use": None,
                           "peak_bytes_in_use": None, "devices": []},
    )
    reg.close()


def test_report_diff_gates_on_regression(tmp_path, capsys):
    from neutronstarlite_tpu.tools.metrics_report import main as report_main

    a, b_ok, b_bad = (tmp_path / n for n in ("a", "b_ok", "b_bad"))
    for d in (a, b_ok, b_bad):
        d.mkdir()
    _write_summary_stream(a / "s.jsonl", "run-a", 0.100, 1 << 20)
    _write_summary_stream(b_ok / "s.jsonl", "run-ok", 0.102, 1 << 20)
    _write_summary_stream(b_bad / "s.jsonl", "run-bad", 0.150, 2 << 20)

    rc = report_main(["--diff", str(a), str(b_ok), "--tol", "0.05"])
    out = capsys.readouterr()
    assert rc == 0
    assert "warm_median_epoch_s" in out.out and "REGRESSED" not in out.out

    rc = report_main(["--diff", str(a), str(b_bad), "--tol", "0.05"])
    out = capsys.readouterr()
    assert rc == 2
    assert "REGRESSED" in out.out
    assert "REGRESSION" in out.err
    # wire bytes doubled AND warm time +50%: both named
    assert "wire_bytes_fwd" in out.err
    assert "warm_median_epoch_s" in out.err

    # identical runs pass at zero tolerance
    rc = report_main(["--diff", str(a), str(a), "--tol", "0"])
    capsys.readouterr()
    assert rc == 0


# ---- distributed trace context (cross-process propagation) ------------------


def test_trace_context_header_roundtrip():
    from neutronstarlite_tpu.obs.trace import TraceContext

    ctx = TraceContext("run:q7", "span-3")
    hdrs = ctx.to_headers(send_ts=1700000000.25)
    assert hdrs == {
        "X-NTS-Trace-Id": "run:q7",
        "X-NTS-Parent-Span": "span-3",
        "X-NTS-Send-Ts": "1700000000.250000",
    }
    back = TraceContext.from_headers(hdrs)
    assert back.trace_id == "run:q7" and back.span_id == "span-3"
    assert back.send_ts == pytest.approx(1700000000.25)
    assert back.recv_ts is not None  # stamped at extraction

    # a root context has no parent span -> the parent header is omitted
    root = TraceContext("run:q7", None)
    assert "X-NTS-Parent-Span" not in root.to_headers()
    # untraced request: no trace header -> no context
    assert TraceContext.from_headers({}) is None
    # case-insensitive extraction (http.server lowercases nothing, but
    # proxies may): the dict-like with .get is all we require
    assert TraceContext.from_headers(
        {"X-NTS-Trace-Id": "t"}).trace_id == "t"


def test_spans_emitted_under_remote_ctx_carry_link_stamps(tmp_path):
    """A span completed with ctx= adopts the remote trace id + parent
    and records the send/recv wall stamps — the join key and the clock
    pair the fleet merge needs."""
    from neutronstarlite_tpu.obs.trace import TraceContext

    reg = registry.MetricsRegistry("replica", algorithm="A",
                                   fingerprint="f",
                                   path=str(tmp_path / "r.jsonl"))
    tr = Tracer(reg)
    ctx = TraceContext.from_headers(
        TraceContext("router-run:q1", "post-7").to_headers())
    with tr.span("predict_handler", cat="serve", ctx=ctx):
        tr.complete("request", dur_s=0.01, graph_seq=5, model_seq=2)
    reg.close()
    evs = _events_of(tmp_path / "r.jsonl")
    handler = next(e for e in evs if e["name"] == "predict_handler")
    assert handler["trace_id"] == "router-run:q1"
    assert handler["parent_id"] == "post-7"
    assert handler["send_ts"] is not None
    assert handler["recv_ts"] >= handler["send_ts"] - 1e-6
    # the nested span inherits the remote trace through the stack
    inner = next(e for e in evs if e["name"] == "request")
    assert inner["trace_id"] == "router-run:q1"
    assert inner["parent_id"] == handler["span_id"]
    assert inner["graph_seq"] == 5 and inner["model_seq"] == 2
    assert schema.validate_stream(evs) == len(evs)


# ---- live spans of the funnel and the run loops (ISSUE 24) ------------------

# the stage vocabulary of an epoch, in the order a loop opens them
# (docs/OBSERVABILITY.md, Tracing); accuracy_dispatch / host_accuracy only
# where the loop has a cadence accuracy (counted on the device, ISSUE 32: no
# logits_copy), epoch_key only where the host derives a key per epoch (the
# sampled scan takes the run's key whole)
EPOCH_STAGES = {
    "fullbatch": ["epoch_key", "step_dispatch", "accuracy_dispatch",
                  "step_device", "loss_fetch", "epoch_emit", "host_accuracy",
                  "ckpt_epoch_end"],
    "dist": ["epoch_key", "step_dispatch", "step_device", "loss_fetch",
             "epoch_emit", "ckpt_epoch_end"],
    "sampled": ["step_dispatch", "step_device", "loss_fetch", "epoch_emit",
                "ckpt_epoch_end"],
}
FUNNEL_PHASES = {  # both GCN trainers hoist the input aggregate, and
    # both build ELL tables, whose padding the tables_stats span carries
    "fullbatch": {"tune_resolve", "tables_build", "tables_stats", "params_init",
                  "datum_upload", "input_aggregate", "step_build"},
    "dist": {"tune_resolve", "dist_graph_build", "dist_tables_build",
             "tables_stats", "datum_upload", "params_init", "input_aggregate",
             "step_build"},
    "sampled": {"tune_resolve"},  # its datum uploads at the first step
}


def _loop_trainer(family, epochs=2):
    """A trainer of one run-loop family through from_arrays(host_graph=),
    large enough that an epoch is milliseconds, not span overhead."""
    from neutronstarlite_tpu.graph.dataset import GNNDatum
    from neutronstarlite_tpu.graph.storage import build_graph
    from neutronstarlite_tpu.graph.synthetic import planted_partition_graph
    from neutronstarlite_tpu.models.base import get_algorithm
    from neutronstarlite_tpu.utils.config import InputInfo

    v_num, f, classes = 6000, 192, 4
    src, dst, feature, label = planted_partition_graph(
        v_num, classes, avg_degree=10, feature_size=f, seed=3
    )
    datum = GNNDatum(
        feature=feature, label=label.astype(np.int32),
        mask=(np.arange(v_num) % 3).astype(np.int32),
    )
    cfg = InputInfo()
    cfg.vertices = v_num
    cfg.layer_string = f"{f}-64-{classes}"
    cfg.epochs = epochs
    cfg.learn_rate = 0.01
    cfg.decay_epoch = -1
    cfg.drop_rate = 0.1
    if family == "fullbatch":
        cfg.algorithm = "GCNCPU"
        cfg.optim_kernel = True
    elif family == "dist":
        cfg.algorithm = "GCNDIST"
        cfg.partitions = 2
        cfg.optim_kernel = True
    else:
        cfg.algorithm = "GCNSAMPLESINGLE"
        cfg.fanout_string = "4-4"
        cfg.batch_size = 128
        cfg.sample_pipeline = "fused"
    host_graph = build_graph(src, dst, v_num, weight="gcn_norm")
    return get_algorithm(cfg.algorithm).from_arrays(
        cfg, None, None, datum, seed=0, host_graph=host_graph
    )


def _spans(trainer):
    return trainer.metrics.flight.records("span")


@pytest.fixture(scope="module", params=sorted(EPOCH_STAGES))
def loop_run(request):
    """One two-epoch run() per run-loop family: the trainer, the stages its
    loop passed to emit_epoch, and the span / epoch records of that run
    (snapshots: a later test runs the trainer again)."""
    trainer = _loop_trainer(request.param)
    passed = []
    inner = trainer.emit_epoch

    def spy(epoch, seconds, loss=None, stages=None, **extra):
        passed.append(dict(stages))
        return inner(epoch, seconds, loss, stages=stages, **extra)

    trainer.emit_epoch = spy
    trainer.run()
    return {
        "family": request.param, "trainer": trainer, "passed": list(passed),
        "spans": _spans(trainer),
        "events": trainer.metrics.flight.records("epoch"),
    }


def test_epoch_spans_hold_the_stage_vocabulary(loop_run):
    family, spans = loop_run["family"], loop_run["spans"]
    epochs = [s for s in spans if s["name"] == "epoch"]
    assert [e["epoch"] for e in epochs] == [0, 1]
    run = next(s for s in spans if s["name"] == "run")
    for e in epochs:
        assert e["cat"] == "epoch" and e["parent_id"] == run["span_id"]
        kids = sorted(
            (s for s in spans if s["parent_id"] == e["span_id"]
             and s["cat"] == "stage"),
            key=lambda s: s["t0"],
        )
        assert [k["name"] for k in kids] == EPOCH_STAGES[family]
        assert all(k["epoch"] == e["epoch"] for k in kids)
        end = e["t0"]
        for k in kids:  # inside the parent, in order, never overlapping
            assert k["t0"] >= end - 1e-9
            end = k["t0"] + k["dur_s"]
        assert end <= e["t0"] + e["dur_s"] + 1e-9
        covered = sum(k["dur_s"] for k in kids)
        assert covered == pytest.approx(e["dur_s"], rel=0.05)


def test_stages_passed_to_emit_epoch_are_the_live_spans(loop_run):
    passed, spans, events = (loop_run[k] for k in ("passed", "spans", "events"))
    for name in ("step_dispatch", "step_device"):
        live = [s["dur_s"] for s in spans if s["name"] == name]
        assert [p[name] for p in passed] == pytest.approx(live, abs=1e-12)
        assert [e["stages"][name] for e in events] == pytest.approx(live)
    # the epoch event keeps its meaning (start to the loss fetch); the span
    # covers the whole iteration
    for e, s in zip(events, (s for s in spans if s["name"] == "epoch")):
        assert e["seconds"] <= s["dur_s"]
        assert e["seconds"] >= sum(
            e["stages"][n] for n in ("step_dispatch", "step_device")
        )


def test_funnel_phases_are_children_of_run(loop_run):
    family, trainer, spans = (loop_run[k] for k in ("family", "trainer", "spans"))
    run = next(s for s in spans if s["name"] == "run")
    first_epoch = next(s for s in spans if s["name"] == "epoch")
    funnel = [
        s for s in spans if s["cat"] == "phase"
        and s["t0"] + s["dur_s"] <= first_epoch["t0"]
    ]
    assert {s["name"] for s in funnel} == FUNNEL_PHASES[family]
    assert all(s["parent_id"] == run["span_id"] for s in funnel)
    # host_graph= was handed in: the funnel built none
    assert "host_graph_build" not in {s["name"] for s in spans}
    phases = trainer.run_summary_record["phases"]
    assert FUNNEL_PHASES[family] <= set(phases)
    if family == "sampled":  # lazy: first touched inside the first dispatch
        assert phases["datum_upload"]["count"] == 2


def test_run_level_stages_and_a_second_run(loop_run):
    family, trainer, spans = (loop_run[k] for k in ("family", "trainer", "spans"))
    run = next(s for s in spans if s["name"] == "run")
    around = [s["name"] for s in spans
              if s["parent_id"] == run["span_id"] and s["cat"] == "stage"]
    assert around == ["run_begin", "ckpt_begin", "ckpt_final", "final_eval",
                      "finalize_metrics"]
    final = next(s for s in spans if s["name"] == "final_eval")
    kids = [s["name"] for s in spans
            if s["parent_id"] == final["span_id"] and s["cat"] != "compile"]
    assert kids == {
        "fullbatch": ["eval_forward", "host_accuracy"],
        "dist": ["eval_forward", "host_accuracy"], "sampled": [],
    }[family]
    # a second run() on the finished trainer (the benchmark's window after
    # its warm-up) reopens the root: its epochs are emitted and parented
    n_before = len(_spans(trainer))
    trainer.run()
    later = _spans(trainer)[n_before:]
    roots = [s for s in later if s["name"] == "run"]
    assert len(roots) == 1 and trainer._run_span is None
    epochs = [s for s in later if s["name"] == "epoch"]
    assert [e["epoch"] for e in epochs] == [0, 1]
    assert all(e["parent_id"] == roots[0]["span_id"] for e in epochs)


def _nts_events(trace_dir):
    """[(name, start_ns, end_ns, stats)] of the ``nts:`` host events."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(
        str(trace_dir), "plugins", "profile", "*", "*.xplane.pb"
    ))
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("nts:"):
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats)))
    return out


@pytest.mark.parametrize("nts_trace", ["1", "0"])
def test_live_spans_annotate_a_profiler_session_they_did_not_start(
    tmp_path, monkeypatch, nts_trace
):
    """The test starts jax.profiler itself (as the benchmark and an
    operator's capture do); NTS_PROFILE_DIR is not set. Live spans land in
    the trace as nts:<name> with the epoch stat; NTS_TRACE=0 leaves no
    record and no event."""
    import jax

    from tests.test_models import _planted_cfg, _planted_data
    from neutronstarlite_tpu.models.gcn import GCNTrainer

    monkeypatch.delenv("NTS_PROFILE_DIR", raising=False)
    monkeypatch.setenv("NTS_TRACE", nts_trace)
    src, dst, datum = _planted_data(seed=2)
    trainer = GCNTrainer.from_arrays(_planted_cfg(epochs=2), src, dst, datum)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        trainer.run()
    finally:
        jax.profiler.stop_trace()
    events = _nts_events(tmp_path)
    if nts_trace == "0":
        assert events == [] and _spans(trainer) == []
        assert len(trainer.metrics.flight.records("epoch")) == 2
        return
    epochs = sorted(e for e in events if e[0] == "nts:epoch")
    assert [e[3]["epoch"] for e in epochs] == [0, 1]
    for name, lo, hi, stats in epochs:
        inside = [e for e in events
                  if e[0] in ("nts:step_dispatch", "nts:step_device")
                  and lo <= e[1] and e[2] <= hi]
        assert [e[0] for e in sorted(inside, key=lambda e: e[1])] == [
            "nts:step_dispatch", "nts:step_device"]
        assert all(e[3]["epoch"] == stats["epoch"] for e in inside)
    # record names stay bare; only the annotation carries the prefix
    assert {"epoch", "step_dispatch", "final_eval"} <= {
        s["name"] for s in _spans(trainer)}
    assert "nts:final_eval" in {e[0] for e in events}


def test_alignment_anchors_on_step_device_not_the_epoch_span_end(tmp_path):
    """Every rank leaves step_device at the same barrier; the epoch span
    ends after a rank-0-only checkpoint write. Two ranks with the SAME
    clock but a slow rank-0 epoch tail must not be shifted apart."""
    paths = []
    for rank, tail in ((0, 0.4), (1, 0.0)):
        p = str(tmp_path / f"r{rank}.jsonl")
        with open(p, "w") as fh:
            for i in range(3):
                t0 = 10.0 + i
                for name, cat, dur in (
                    ("step_device", "stage", 0.5),
                    ("epoch", "epoch", 0.5 + tail),
                ):
                    fh.write(json.dumps({
                        "event": "span", "run_id": f"run{rank}", "schema": 1,
                        "ts": 1000.0 + t0 + dur, "seq": 2 * i,
                        "name": name, "cat": cat, "span_id": f"{name}{i}",
                        "trace_id": "t", "parent_id": None, "t0": t0,
                        "dur_s": dur, "rank": rank, "epoch": i,
                    }) + "\n")
        paths.append(p)
    streams = trace_timeline.load_streams(paths)
    assert [s.align for s in streams] == pytest.approx([0.0, 0.0], abs=1e-9)


# ---- every compile as a span (obs/compiles) --------------------------------
# The listener is process-wide and stays once installed (an entry point's
# configure_compile_cache() installs it), so every test here and above holds
# with it on.


@pytest.fixture
def traced():
    """(registry, tracer) with the compile listener installed."""
    from neutronstarlite_tpu.obs import compiles

    compiles.install()
    reg = registry.MetricsRegistry("t", algorithm="A", fingerprint="f")
    return reg, Tracer(reg)


@pytest.fixture
def persistent_cache(tmp_path):
    """JAX's persistent compile cache on, in a directory of this test."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    before = {
        name: getattr(jax.config, name) for name in (
            "jax_enable_compilation_cache", "jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    }
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    cc.reset_cache()
    yield str(tmp_path / "cache")
    for name, value in before.items():
        jax.config.update(name, value)
    cc.reset_cache()


def _fresh_step(tag):
    """A jitted function no test has compiled yet (its name and its
    constant make the program new), which calls a jitted function."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def inner(x):
        return x @ x

    def _step(x):
        return jnp.sum(inner(x)) + float(len(tag))

    _step.__name__ = f"_step_{tag}"
    return jax.jit(_step), jnp.ones((32, 32)), f"jit(_step_{tag})"


def _compile_spans(reg, fun=None):
    return [s for s in reg.flight.records("span") if s["cat"] == "compile"
            and (fun is None or s["fun"] == fun)]


def test_a_first_call_under_an_open_span_is_one_compile_span(traced, persistent_cache):
    import jax

    reg, tr = traced
    f, x, fun = _fresh_step("miss_then_hit")
    with tr.span("step_dispatch", cat="stage") as parent:
        f(x).block_until_ready()
    (miss,) = _compile_spans(reg, fun)
    assert miss["name"] == "compile" and miss["parent_id"] == parent.span_id
    assert miss["cache"] == "miss" and miss["retrieve_s"] == 0.0
    assert miss["dur_s"] == pytest.approx(
        miss["trace_s"] + miss["lower_s"] + miss["backend_s"], abs=1e-12)
    assert min(miss["trace_s"], miss["lower_s"], miss["backend_s"]) > 0.0
    # on the clock of the live spans, and inside its parent but for its
    # start: a retroactive span starts at its end less the three durations
    assert miss["t0"] + miss["dur_s"] <= parent.t0 + parent.dur_s
    assert miss["t0"] + miss["dur_s"] >= parent.t0
    assert schema.validate_event(miss) is None

    f(x)  # compiled: JAX reports nothing, so there is nothing to add
    assert len(_compile_spans(reg, fun)) == 1

    jax.clear_caches()  # the same program again: the persistent cache has it
    with tr.span("step_dispatch", cat="stage"):
        f(x).block_until_ready()
    _, hit = _compile_spans(reg, fun)
    assert hit["cache"] == "hit" and 0.0 < hit["retrieve_s"] <= hit["backend_s"]
    assert hit["dur_s"] == pytest.approx(
        hit["trace_s"] + hit["lower_s"] + hit["backend_s"], abs=1e-12)
    got = reg.snapshot()["counters"]
    assert got["compile.cache_hits"] >= 1 and got["compile.cache_misses"] >= 1
    assert got["compile.requests"] == got["compile.cache_hits"] + got["compile.cache_misses"]
    assert got["compile.backend_s"] >= miss["backend_s"] + hit["backend_s"] - 1e-9
    assert got["compile.retrieve_s"] >= hit["retrieve_s"] - 1e-9


def test_the_trace_seconds_are_the_lowered_functions_not_a_sum(traced):
    """``inner`` reports a trace duration of its own before ``_step``'s:
    the span is ``_step``'s alone, and with the cache off says ``off``."""
    from jax import monitoring
    from jax._src import monitoring as monitoring_src

    from neutronstarlite_tpu.obs import compiles

    reg, tr = traced
    f, x, fun = _fresh_step("nested")
    seen = []

    def spy(name, seconds, fun_name="", **_):
        if name == compiles.TRACE:
            seen.append((fun_name, seconds))

    monitoring.register_event_duration_secs_listener(spy)
    try:
        with tr.span("step_dispatch", cat="stage"):
            f(x).block_until_ready()
    finally:
        monitoring_src.unregister_event_duration_listener(spy)
    (span,) = _compile_spans(reg, fun)
    assert span["cache"] == "off"
    assert "inner" in [name for name, _ in seen]
    assert span["trace_s"] == dict(seen)["_step_nested"]
    assert not [s for s in _compile_spans(reg) if s["fun"] == "jit(inner)"]


def test_a_compile_with_no_span_open_is_neither_spanned_nor_counted(traced):
    """A caller's own programs (the benchmark's check, after ``run()`` has
    closed its root) are not the run's: the ring and the counters hold none."""
    reg, tr = traced
    f, x, fun = _fresh_step("outside")
    f(x).block_until_ready()
    assert not _compile_spans(reg)
    assert not [k for k in reg.snapshot()["counters"] if k.startswith("compile.")]


def test_nts_trace_0_gives_no_compile_span_and_keeps_the_counters(monkeypatch):
    from neutronstarlite_tpu.obs import compiles

    monkeypatch.setenv("NTS_TRACE", "0")
    compiles.install()
    reg = registry.MetricsRegistry("t", algorithm="A", fingerprint="f")
    tr = Tracer(reg)
    f, x, fun = _fresh_step("untraced")
    with tr.span("step_dispatch", cat="stage"):
        f(x).block_until_ready()
    assert not reg.flight.records("span")
    got = reg.snapshot()["counters"]
    assert got["compile.requests"] >= 1 and got["compile.backend_s"] > 0.0


def test_a_compile_inside_an_outer_trace_leaves_the_outer_ones_seconds(traced):
    """``jax.ensure_compile_time_eval`` compiles and runs ``inner`` while
    ``_step`` is traced: that request ends before ``_step``'s trace does,
    and ``_step``'s span still carries its own trace seconds."""
    import jax
    import jax.numpy as jnp

    reg, tr = traced

    @jax.jit
    def _eager_inner(x):
        return x * 3.0 + 1.0

    def _step_around(x):
        with jax.ensure_compile_time_eval():
            c = _eager_inner(jnp.ones((4,)))
        return jnp.sum(x) + c[0]

    with tr.span("step_dispatch", cat="stage") as parent:
        jax.jit(_step_around)(jnp.ones((8,))).block_until_ready()
    by_fun = {s["fun"]: s for s in _compile_spans(reg)}
    inner, outer = by_fun["jit(_eager_inner)"], by_fun["jit(_step_around)"]
    assert inner["parent_id"] == outer["parent_id"] == parent.span_id
    assert inner["t0"] + inner["dur_s"] <= outer["t0"] + outer["dur_s"]
    assert outer["trace_s"] > inner["trace_s"] > 0.0  # the outer trace holds the whole inner request
    assert outer["trace_s"] >= inner["dur_s"]


def test_the_listener_pairs_a_lowering_with_its_trace_across_a_nested_request(traced):
    """The events as JAX sends them when a program compiles while an outer
    one is being lowered: the inner request ends first and does not take
    the outer function's trace with it; a trace no lowering claims (an
    ``eval_shape``) is dropped once ``TRACES_KEPT`` newer ones came."""
    from neutronstarlite_tpu.obs import compiles

    reg, tr = traced
    with tr.span("step_dispatch", cat="stage"):
        compiles._on_duration(compiles.TRACE, 0.5, fun_name="outer_fn")
        compiles._on_duration(compiles.TRACE, 0.001, fun_name="inner_fn")  # inside the lowering
        compiles._on_duration(compiles.LOWER, 5.0, fun_name="jit(inner_fn)")
        compiles._on_duration(compiles.BACKEND, 0.25, fun_name="jit(inner_fn)")
        compiles._on_duration(compiles.LOWER, 0.0, fun_name="jit(outer_fn)")
        compiles._on_duration(compiles.BACKEND, 2.0, fun_name="jit(outer_fn)")
    by_fun = {s["fun"]: s for s in _compile_spans(reg)}
    assert by_fun["jit(outer_fn)"]["trace_s"] == 0.5
    assert by_fun["jit(outer_fn)"]["dur_s"] == pytest.approx(2.5)
    assert by_fun["jit(inner_fn)"]["trace_s"] == 0.0  # traced after its lowering's start: not its own
    for i in range(3 * compiles.TRACES_KEPT):
        compiles._on_duration(compiles.TRACE, 0.1, fun_name=f"shape_only_{i}")
    assert len(compiles._pending.traced) == compiles.TRACES_KEPT
    compiles._pending.traced.clear()


def test_a_listener_that_raises_is_logged_and_never_fails_the_compile(traced, monkeypatch):
    from neutronstarlite_tpu.obs import compiles

    reg, tr = traced

    def broken():
        raise RuntimeError("no tracer today")

    monkeypatch.setattr(compiles.trace, "on_thread", broken)
    f, x, fun = _fresh_step("listener_raises")
    with tr.span("step_dispatch", cat="stage"):
        assert float(f(x)) == 32.0 * 32.0 * 32.0 + len("listener_raises")
    assert not _compile_spans(reg)
    compiles._on_duration(compiles.TRACE, "not a number", fun_name="f")  # every branch is guarded
    compiles._on_event(compiles.HIT, unexpected=object())


def test_the_span_goes_through_the_tracer_the_thread_has_a_span_open_under(traced):
    """A train-then-serve process has two tracers over one registry: the
    newer one does not take the older one's compiles from it."""
    reg, older = traced
    newer = Tracer(reg)
    f, x, fun = _fresh_step("two_tracers")
    with older.span("step_dispatch", cat="stage") as parent:
        f(x).block_until_ready()
    (span,) = _compile_spans(reg, fun)
    assert span["parent_id"] == parent.span_id and newer.current() is None


def test_a_span_deferred_before_the_first_tracer_is_emitted_once_as_a_root(monkeypatch):
    from neutronstarlite_tpu.obs import trace

    monkeypatch.setattr(trace, "_tracers", [])
    monkeypatch.setattr(trace, "_newest", None)
    monkeypatch.setattr(trace, "_deferred", [])
    assert trace.newest() is None and trace.on_thread() is None
    trace.defer("process_prelude", 10.0, 2.5, cat="startup", backend_live=1)
    reg = registry.MetricsRegistry("t", algorithm="A", fingerprint="f")
    first = Tracer(reg)
    assert trace.newest() is first
    Tracer(registry.MetricsRegistry("u", algorithm="A", fingerprint="f"))
    (span,) = reg.flight.records("span")
    assert (span["name"], span["cat"], span["t0"], span["dur_s"], span["parent_id"],
            span["backend_live"]) == ("process_prelude", "startup", 10.0, 2.5, None, 1)
    # NTS_TRACE=0: dropped with every other span, not kept for a later tracer
    trace.defer("process_prelude", 10.0, 2.5, cat="startup")
    monkeypatch.setenv("NTS_TRACE", "0")
    off = registry.MetricsRegistry("v", algorithm="A", fingerprint="f")
    Tracer(off)
    assert not off.flight.records("span") and not trace._deferred


def _seq_trainer(tmp_path):
    from test_seqlm import make_trainer

    return make_trainer(tmp_path)


@pytest.mark.parametrize("family, step", [
    ("fullbatch", "train_step"), ("seqlm", "_step"),
])
def test_the_first_epoch_holds_the_steps_compile_and_the_second_none(
        family, step, tmp_path):
    from neutronstarlite_tpu.obs import compiles

    compiles.install()
    trainer = _seq_trainer(tmp_path) if family == "seqlm" else _loop_trainer(family)
    trainer.run()
    spans = _spans(trainer)
    by_id = {s["span_id"]: s for s in spans}
    first, second = [s for s in spans if s["name"] == "epoch"]

    def under(span, epoch):
        while span is not None and span["span_id"] != epoch["span_id"]:
            span = by_id.get(span["parent_id"])
        return span is not None

    compiled = [s for s in spans if s["cat"] == "compile"]
    in_first = [s for s in compiled if under(s, first)]
    the_step = [s for s in in_first if step in s["fun"]]
    assert len(the_step) == 1, [s["fun"] for s in in_first]
    assert by_id[the_step[0]["parent_id"]]["name"] == "step_dispatch"
    assert the_step[0]["backend_s"] > 0.0 and the_step[0]["trace_s"] > 0.0
    assert not [s for s in compiled if under(s, second)]
    # every compile of the run is under a span of the run, the ring kept its
    # first record, and the summary carries the counters
    assert all(s["parent_id"] in by_id for s in compiled)
    assert trainer.metrics.flight.records("run_start")
    assert len(compiled) < 400
    summary = trainer.run_summary_record
    cache = summary["compile_cache"]
    assert set(cache) == {"persistent_cache_dir", "enabled", "requests", "hits", "misses",
                          "trace_s", "lower_s", "backend_s", "retrieve_s"}
    assert cache["requests"] >= len(compiled) >= 1
    assert cache["backend_s"] >= sum(s["backend_s"] for s in compiled) - 1e-6
    assert summary["counters"]["compile.requests"] == cache["requests"]
    assert schema.validate_event(summary) is None


def test_the_newest_tracers_registry_is_found_after_its_trainer_is_gone():
    """The benchmark's readers run when the trainer has gone out of scope:
    the ring is held by obs/flight, the registry (its gauges) through the
    newest tracer."""
    import gc

    from neutronstarlite_tpu.obs import trace

    reg = registry.MetricsRegistry("t", algorithm="A", fingerprint="f")
    reg.gauge_set("step.generated_code_bytes", 345)
    tr = Tracer(reg)
    del reg, tr
    gc.collect()
    gauges = trace.newest().registry.snapshot(include_hists=False)["gauges"]
    assert gauges["step.generated_code_bytes"] == 345
