"""Schema round-trip: every typed record kind constructs, validates, and
report-renders.

The contract this file enforces: ``obs/schema.KNOWN_KINDS`` is the closed
list of typed records, and EVERY kind must have (a) a factory here that
builds a valid instance, (b) an entry in RENDER_MARKERS naming the string
its renderer leaves in the metrics_report output (None only for records
whose rendering story is explicitly "envelope-only"). Adding a record kind
to the schema without extending this file — or without renderer support —
fails tier-1 instead of shipping silently unrenderable telemetry.
"""

from __future__ import annotations

import json

import pytest

from neutronstarlite_tpu.obs import registry, schema

# ---- one factory per typed kind (emitted through a real registry so the
# envelope is the production one) --------------------------------------------


def _emit_all(reg: registry.MetricsRegistry) -> None:
    reg.event("run_start", algorithm="GCNDIST", fingerprint="cafecafecafe",
              seed=0, process_index=0, pid=1234)
    reg.event("epoch", epoch=0, seconds=0.5, loss=1.25)
    reg.event("epoch_scan", bucket=4, batches=4, dispatches=1,
              h2d_bytes=0, epoch=0, seconds=0.12)
    reg.event("ring_step", epoch=0, step=1, bytes=4096, skipped=False,
              seconds=None, epoch_span="s1")
    reg.event("fault", kind="nonfinite_loss", epoch=1, attempt=1,
              injected=True)
    reg.event("recovery", action="rollback", epoch=1, attempt=1)
    reg.event("heartbeat", partition=0, epoch=0)
    reg.event("rank_loss", partition=2, epoch=1, reason="heartbeat_miss",
              missed_beats=3)
    reg.event("replan", from_partitions=4, to_partitions=3, lost=2,
              seconds=0.25, moved_vertices=1200)
    reg.event("serve_request", n_seeds=2, status="ok", total_ms=3.5,
              queue_ms=1.0, req_id="q1", flush_id=0)
    reg.event("batch_flush", n_requests=1, n_seeds=2, reason="deadline",
              bucket=4, exec_ms=2.0, flush_id=0)
    reg.event("shed", reason="queue_full (depth 8)", queue_depth=8,
              req_id="q2")
    reg.event(
        "tune_trial", family="dist_dense/DistGCNTrainer",
        candidate="ring_blocked|-|-|bf16", source="measured",
        seconds=0.012, predicted_bytes=123456, partitions=4,
    )
    reg.event(
        "tune_decision", family="dist_dense/DistGCNTrainer",
        candidate="ring_blocked|-|-|bf16", source="measured",
        seconds=0.012, predicted_bytes=123456, partitions=4,
        decision={"dist_path": "ring_blocked", "kernel": "",
                  "ell_levels": "", "wire_dtype": "bf16"},
    )
    reg.event(
        "graph_delta", added_edges=3, removed_edges=1, added_vertices=0,
        graph_digest="cafe" * 16, cache_invalidated=4, rows_patched=2,
        dirty_predictions=9, seconds=0.012, replica="r0",
    )
    reg.event(
        "serve_summary", requests=1, shed=1,
        latency_ms={"p50": 3.5, "p95": 3.5, "p99": None},
        throughput_rps=10.0, counters={"serve.requests": 1},
    )
    reg.event(
        "span", name="epoch", cat="epoch", span_id="s1",
        trace_id=reg.run_id, parent_id=None, t0=10.0, dur_s=0.5,
        rank=0, thread="MainThread", epoch=0,
        # remote-parent link stamps + freshness lineage (the distributed
        # tracing fields a cross-host serve request carries)
        send_ts=1700000000.25, recv_ts=1700000000.75,
        graph_seq=3, model_seq=1,
    )
    reg.event("stream_rotated",
              reason="NTS_METRICS_MAX_MB: stream exceeded 1 MB",
              rotated_to="x.jsonl.1", bytes_written=1048600)
    reg.event(
        "hist", name="serve.latency_ms", unit="ms", growth=1.02,
        min_value=0.001, count=3, sum=10.5, zero_count=0,
        min=2.0, max=5.0, buckets=[[340, 2], [367, 1]],
    )
    reg.event(
        "slo_status", objective="serve_p99_ms<=75@5m",
        metric="serve_p99_ms", state="breach", threshold=75.0,
        window_s=300.0, value=120.0, burn_rate=3.2, burn_rate_short=4.1,
        window_count=420,
    )
    reg.event(
        "program_cost", label="serve.bucket_16", available=True,
        source="compiled", flops=528383.0, bytes_accessed=65580.0,
        transcendentals=None,
        memory={"argument_bytes": 16384, "output_bytes": 4,
                "temp_bytes": 16400, "alias_bytes": 0,
                "generated_code_bytes": None, "peak_bytes": 32788},
        platform="cpu",
    )
    reg.event(
        "tensor_stats", name="grads/l0", epoch=2, finite_fraction=1.0,
        absmax=0.125, rms=0.004, zero_fraction=0.25,
    )
    reg.event(
        "tensor_stats", name="wire/l0", epoch=2, finite_fraction=1.0,
        absmax=2.5, rms=0.9, zero_fraction=0.0, quant_rel_err=0.0016,
    )
    reg.event(
        "nonfinite_provenance", fault_kind="nonfinite_loss", epoch=2,
        layer=1, op="activation", name="acts/l1", finite_fraction=0.0,
        checked=4, injected=True,
    )
    reg.event(
        "model_drift", metric="tune_prior_ranking", source="tune_prior",
        predicted=0.040, observed=0.080, drift=1.0, threshold=0.1,
        family="dist_dense/DistGCNTrainer", partitions=4,
        candidate="all_gather|-|-|-", measured_best="ring_blocked|-|-|bf16",
        flagged_entry="tune-cafecafecafecafe.json",
    )
    reg.event(
        "telemetry", source="hub", counters={"hub.polls": 3.0},
        gauges={"hub.targets": 3, "hub.targets_ok": 2,
                "hub.targets_lost": 1},
        slo={"objectives": 2, "breaching": 0, "worst": "ok"},
        targets=3, targets_ok=2, targets_lost=1, uptime_s=12.5,
    )
    reg.event(
        "target_loss", target="http://host2:9100/telemetry",
        reason="poll_miss", missed_polls=3, miss_k=3,
        last_ok_ts=1700000000.0,
    )
    reg.event(
        "straggler", partition=2, epoch=5, seconds=1.9, median_s=1.0,
        mad_s=0.0, threshold_s=1.25, excess=0.9, consecutive=3,
        source="partition_step",
    )
    reg.event(
        "rollout", ckpt_dir="/ckpt/step-5", verdict="promoted",
        ckpt_step=5, replicas=3, restarted=3, rolled_back=0,
        canary={"disagreement": 0.0, "tolerance": 0.05, "seeds": 32,
                "passed": True},
        seconds=4.2, error=None,
    )
    reg.event(
        "delta_commit", seq=3, writer="w1", writer_seq=2, added_edges=4,
        removed_edges=1, added_vertices=1, graph_digest="feed" * 16,
        dirty=12, dirty_mode="bitset", fp_rate=0.05, seconds=0.004,
    )
    reg.event(
        "finetune_round", round=0, seq_lo=1, seq_hi=3, dirty=12, epochs=2,
        batches=6, loss=0.42, ckpt_step=7, verdict="promoted",
        seconds=1.25,
    )
    reg.event(
        "run_summary", algorithm="GCNDIST", fingerprint="cafecafecafe",
        counters={"wire.bytes_fwd": 4096}, gauges={}, timings={},
        epochs=1,
        epoch_time={"first_s": 0.5, "warm_median_s": None,
                    "compile_overhead_s": None},
        avg_epoch_s=0.5, epoch_times_s=[0.5], loss_history=[1.25],
        phases={}, memory={"available": False, "bytes_in_use": None,
                           "peak_bytes_in_use": None, "devices": []},
        device={"platform": "tpu", "device_kind": "TPU v5 lite", "count": 4},
    )


# the string each kind's renderer leaves in the metrics_report text output.
# None is an EXPLICIT decision that the kind is envelope-only context
# (run_start parameterizes the header; it has no line of its own).
RENDER_MARKERS = {
    "run_start": None,
    "epoch": "#epochs=",
    "epoch_scan": "#epoch_scan=",
    "ring_step": "ring-pipelined exchange:",
    "fault": "kind=nonfinite_loss",
    "recovery": "action=rollback",
    "heartbeat": "#heartbeats=",
    "rank_loss": "#rank_loss=",
    "replan": "#replan=",
    "serve_request": "finish serving !",
    "batch_flush": "#batches=",
    "shed": "#shed=",
    "serve_summary": "#p99_latency=",
    "graph_delta": "#graph_delta=",
    "tune_trial": "#tune_trials=",
    "tune_decision": "#tune_decision=",
    "span": "span timeline:",
    "stream_rotated": "stream_rotated",
    "hist": "#hist_serve.latency_ms=",
    "slo_status": "slo timeline:",
    "program_cost": "#program_cost=serve.bucket_16",
    "model_drift": "prediction drift:",
    "tensor_stats": "numerics:",
    "nonfinite_provenance": "#nonfinite_provenance=",
    "telemetry": "#telemetry=",
    "target_loss": "#target_loss=",
    "straggler": "#straggler=",
    "rollout": "#rollout=",
    "delta_commit": "#delta_commit=",
    "finetune_round": "#finetune_round=",
    "run_summary": "finish algorithm !",
}


def test_every_known_kind_has_a_factory_and_a_render_decision():
    """The enforcement hook: extend KNOWN_KINDS -> extend this file."""
    assert set(RENDER_MARKERS) == set(schema.KNOWN_KINDS)


def test_roundtrip_construct_validate_render(tmp_path, capsys):
    path = tmp_path / "all_kinds.jsonl"
    reg = registry.MetricsRegistry(
        "gcndist-cafecafecafe-1234", algorithm="GCNDIST",
        fingerprint="cafecafecafe", path=str(path),
    )
    _emit_all(reg)
    reg.close()

    events = [json.loads(line) for line in open(path) if line.strip()]
    # construct -> validate: every KNOWN kind present and schema-valid
    assert schema.validate_stream(events) == len(events)
    assert {e["event"] for e in events} == set(schema.KNOWN_KINDS)

    # -> render: the report CLI accepts the stream and every kind's
    # renderer left its marker
    from neutronstarlite_tpu.tools.metrics_report import main as report_main

    rc = report_main([str(path)])
    out = capsys.readouterr().out
    assert rc == 0
    for kind, marker in RENDER_MARKERS.items():
        if marker is not None:
            assert marker in out, (
                f"record kind {kind!r} left no {marker!r} in the report — "
                "renderer support missing"
            )
    # the run names the device it executed on, as JAX reported it
    assert "#device=tpu TPU v5 lite x4" in out


def test_validator_rejects_mutations_per_kind(tmp_path):
    """Each typed kind's validator actually bites: one representative
    field violation per kind must raise."""
    path = tmp_path / "k.jsonl"
    reg = registry.MetricsRegistry("r", algorithm="A", fingerprint="f",
                                   path=str(path))
    _emit_all(reg)
    reg.close()
    events = {e["event"]: e for e in
              (json.loads(line) for line in open(path) if line.strip())}

    mutations = {
        "run_start": {"algorithm": 7},
        "epoch": {"seconds": 0},
        "epoch_scan": {"dispatches": 0},
        "ring_step": {"step": 0},
        "fault": {"kind": ""},
        "recovery": {"action": ""},
        "heartbeat": {"partition": -1},
        "rank_loss": {"reason": ""},
        "replan": {"from_partitions": 0},
        "serve_request": {"n_seeds": 0},
        "batch_flush": {"reason": ""},
        "shed": {"reason": ""},
        "serve_summary": {"latency_ms": "fast"},
        "graph_delta": {"graph_digest": ""},
        "tune_trial": {"candidate": ""},
        "tune_decision": {"partitions": 0},
        "span": {"dur_s": -1.0},
        "stream_rotated": {"bytes_written": "lots"},
        "hist": {"buckets": [[340, 0]]},
        "slo_status": {"state": ""},
        "program_cost": {"label": ""},
        "model_drift": {"drift": "lots"},
        "tensor_stats": {"finite_fraction": 1.5},
        "nonfinite_provenance": {"checked": -1},
        "telemetry": {"source": ""},
        "target_loss": {"missed_polls": 0},
        "straggler": {"partition": -1},
        "rollout": {"verdict": ""},
        "delta_commit": {"seq": 0},
        "finetune_round": {"epochs": 0},
        "run_summary": {"epoch_time": None},
    }
    assert set(mutations) == set(schema.KNOWN_KINDS)
    for kind, mut in mutations.items():
        bad = dict(events[kind], **mut)
        with pytest.raises(ValueError):
            schema.validate_event(bad)

    # run_summary.device is optional (synthesized summaries lack it) but
    # typed when present
    summ = events["run_summary"]
    schema.validate_event({k: v for k, v in summ.items() if k != "device"})
    for dev in ("tpu", {"platform": "", "device_kind": "x", "count": 1},
                {"platform": "tpu", "device_kind": "x", "count": 0},
                {"platform": "tpu", "count": 1}):
        with pytest.raises(ValueError):
            schema.validate_event(dict(summ, device=dev))

    # the span's distributed-tracing fields bite individually too: the
    # remote-parent stamps must be numbers, the lineage seqs ints
    span = events["span"]
    for mut in ({"send_ts": "noon"}, {"recv_ts": [1.0]},
                {"graph_seq": "3"}, {"model_seq": True},
                {"graph_seq": 2.5}):
        with pytest.raises(ValueError):
            schema.validate_event(dict(span, **mut))
    # ...while absence stays valid (untraced spans carry none of them)
    bare = {k: v for k, v in span.items()
            if k not in ("send_ts", "recv_ts", "graph_seq", "model_seq")}
    schema.validate_event(bare)


def test_stream_only_file_renders_natively(tmp_path, capsys):
    """A file holding only streaming receipts (delta_commit /
    finetune_round with no run_summary, epoch, or serve events — e.g. an
    ingest-sidecar or rotated-away stream) renders the stream block
    natively instead of "skipping", the same courtesy probe-only and
    hub-merged streams get."""
    path = tmp_path / "stream_only.jsonl"
    reg = registry.MetricsRegistry("rs", algorithm="G", fingerprint="f",
                                   path=str(path))
    reg.event("delta_commit", seq=1, writer="w1", writer_seq=1,
              added_edges=2, removed_edges=0, added_vertices=1,
              graph_digest="d1", dirty=5, dirty_mode="exact",
              seconds=0.01)
    reg.event("finetune_round", round=0, seq_lo=1, seq_hi=1, dirty=5,
              epochs=1, batches=3, loss=0.9, ckpt_step=0,
              verdict="promoted", seconds=0.5)
    reg.close()
    from neutronstarlite_tpu.tools.metrics_report import main as report_main

    rc = report_main([str(path)])
    out = capsys.readouterr().out
    assert rc == 0
    assert "== stream" in out
    assert "#delta_commit=seq 1" in out
    assert "#finetune_round=0" in out
