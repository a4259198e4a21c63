"""Async sampling pipeline (sample/pipeline.py + device_sampler.py) tests.

The contract under test (ISSUE 7, docs/SAMPLING.md): pipelined execution
is a pure scheduling change — bitwise-identical training to the
synchronous oracle — with bounded prefetch, loud failure, clean drain,
measurable overlap, and a distribution-faithful on-device fast path.
"""

from __future__ import annotations

import collections
import threading
import time

import numpy as np
import pytest

import jax

from tests.conftest import tiny_graph
from neutronstarlite_tpu.graph.dataset import GNNDatum
from neutronstarlite_tpu.graph.storage import build_graph
from neutronstarlite_tpu.graph.synthetic import planted_partition_graph
from neutronstarlite_tpu.models.gcn_sample import GCNSampleTrainer
from neutronstarlite_tpu.sample.device_sampler import DeviceUniformSampler
from neutronstarlite_tpu.sample.parallel import ParallelEpochSampler
from neutronstarlite_tpu.sample.pipeline import (
    SamplePipeline,
    SampleWorkerError,
    resolve_sample_pipeline,
)
from neutronstarlite_tpu.sample.sampler import SampledBatch, Sampler
from neutronstarlite_tpu.utils.config import InputInfo


def _planted(seed=4, v_num=180, classes=3, f=10):
    src, dst, feature, label = planted_partition_graph(
        v_num, classes, avg_degree=8, feature_size=f, seed=seed
    )
    mask = (np.arange(v_num) % 3).astype(np.int32)
    datum = GNNDatum(
        feature=feature, label=label.astype(np.int32), mask=mask
    )
    host_graph = build_graph(src, dst, v_num, weight="gcn_norm")
    cfg = InputInfo()
    cfg.algorithm = "GCNSAMPLESINGLE"
    cfg.vertices = v_num
    cfg.layer_string = f"{f}-8-{classes}"
    cfg.fanout_string = "3-3"
    cfg.batch_size = 16
    cfg.epochs = 3
    cfg.learn_rate = 0.02
    cfg.drop_rate = 0.0
    cfg.decay_epoch = -1
    return cfg, src, dst, datum, host_graph


def _no_pipeline_threads():
    return not [
        t for t in threading.enumerate()
        if t.name.startswith("sample-pipeline") and t.is_alive()
    ]


class _SleepSource:
    """Deterministic fake batch source with a configurable sample cost."""

    def __init__(self, batches, per_batch_s=0.0, fail_at=None):
        self.batches = batches
        self.per_batch_s = per_batch_s
        self.fail_at = fail_at

    def sample_epoch(self, epoch):
        for i, b in enumerate(self.batches):
            if self.per_batch_s:
                time.sleep(self.per_batch_s)
            if self.fail_at is not None and i == self.fail_at:
                raise RuntimeError(f"boom at batch {i}")
            yield b


@pytest.fixture(scope="module")
def toy_batches(request):
    rng = np.random.default_rng(7)
    g, _ = tiny_graph(rng, v_num=60, e_num=400)
    s = Sampler(g, np.arange(60), batch_size=16, fanouts=[3],
                rng=np.random.default_rng(1))
    return list(s.sample_epoch(shuffle=False))


# ---- scheduling semantics -------------------------------------------------


def test_pipeline_bitwise_parity_full_run(monkeypatch):
    """sync and pipelined runs over ONE shared host graph must be
    bitwise-identical in loss history and parameters — the pipeline may
    change when a batch is produced, never what is produced."""
    monkeypatch.setenv("NTS_SAMPLE_WORKERS", "0")
    monkeypatch.setenv("NTS_FINAL_EVAL", "0")
    cfg, src, dst, datum, host_graph = _planted()

    def run(mode):
        import dataclasses

        c = dataclasses.replace(cfg, sample_pipeline=mode)
        tr = GCNSampleTrainer.from_arrays(
            c, src, dst, datum, seed=0, host_graph=host_graph
        )
        tr.run()
        return tr.loss_history, jax.tree_util.tree_map(np.asarray, tr.params)

    sync_loss, sync_params = run("")
    pipe_loss, pipe_params = run("pipelined")
    assert sync_loss == pipe_loss
    for a, b in zip(sync_params, pipe_params):
        np.testing.assert_array_equal(a["W"], b["W"])
    assert _no_pipeline_threads()


def test_pipeline_matches_source_order(toy_batches):
    """Every batch, in order, across epochs — including the cross-epoch
    prefetch path (the whole range is scheduled up front)."""
    src = ParallelEpochSampler(
        tiny_graph(np.random.default_rng(7), v_num=60, e_num=400)[0],
        np.arange(60), 16, [3], seed=5, workers=0,
    )
    want = [list(src.sample_epoch(e)) for e in range(3)]
    pipe = SamplePipeline(src, range(3), depth=2, transfer=lambda b: b)
    got = [list(pipe.epoch_stream(e)) for e in range(3)]
    pipe.close()
    for we, ge in zip(want, got):
        assert len(we) == len(ge)
        for a, b in zip(we, ge):
            np.testing.assert_array_equal(a.seeds, b.seeds)
            for ha, hb in zip(a.hops, b.hops):
                np.testing.assert_array_equal(ha.src_local, hb.src_local)
                np.testing.assert_allclose(ha.weight, hb.weight)
    assert _no_pipeline_threads()


def test_pipeline_backpressure_bounds_producer(toy_batches):
    """A stalled consumer must backpressure the producer at the queue
    depth — never balloon host memory with padded batches."""
    batches = toy_batches * 5  # 20 batches
    pipe = SamplePipeline(
        _SleepSource(batches), range(1), depth=2, transfer=lambda b: b
    )
    time.sleep(0.6)  # consumer never arrives
    # queue holds `depth`; at most one more batch is sampled and blocked
    # in put(); produced counts only successful puts
    assert pipe.produced <= 2
    got = list(pipe.epoch_stream(0))
    assert len(got) == len(batches)
    assert pipe.peak_depth <= 2
    pipe.close()
    assert _no_pipeline_threads()


def test_pipeline_worker_exception_propagates(toy_batches):
    """A producer exception surfaces as SampleWorkerError (a resilience
    HealthError) at the consumer — promptly, never a hang."""
    from neutronstarlite_tpu.resilience.guards import HealthError

    pipe = SamplePipeline(
        _SleepSource(toy_batches, fail_at=2), range(1),
        depth=2, transfer=lambda b: b,
    )
    t0 = time.perf_counter()
    with pytest.raises(SampleWorkerError, match="boom at batch 2"):
        list(pipe.epoch_stream(0))
    assert time.perf_counter() - t0 < 30.0
    assert issubclass(SampleWorkerError, HealthError)
    pipe.close()
    assert _no_pipeline_threads()


def test_pipeline_drain_on_early_stop(toy_batches):
    """Breaking out of an epoch mid-stream + close() leaves no thread
    behind and unblocks a producer stuck in put()."""
    batches = toy_batches * 5
    pipe = SamplePipeline(
        _SleepSource(batches), range(2), depth=2, transfer=lambda b: b
    )
    stream = pipe.epoch_stream(0)
    next(stream)
    next(stream)  # early stop: 2 of 20 consumed
    pipe.close()
    assert _no_pipeline_threads()
    pipe.close()  # idempotent


def test_pipeline_overlap_hides_sample_time(toy_batches):
    """With sampling and 'compute' each costing T per batch, the pipelined
    consumer's measured stall must be well under the serial sample time
    (the overlap the subsystem exists to buy). Sleep-based, so it holds
    on a single-core rig."""
    n, t = 8, 0.02
    pipe = SamplePipeline(
        _SleepSource(toy_batches[:1] * n, per_batch_s=t), range(1),
        depth=2, transfer=lambda b: b,
    )
    got = 0
    for _ in pipe.epoch_stream(0):
        time.sleep(t)  # the simulated device step
        got += 1
    pipe.close()
    assert got == n
    serial_sample_s = n * t
    assert pipe.stall_s < 0.5 * serial_sample_s, (
        f"stall {pipe.stall_s:.3f}s vs serial sample {serial_sample_s:.3f}s"
    )
    assert _no_pipeline_threads()


def test_pipeline_out_of_order_consumption_refuses(toy_batches):
    src = ParallelEpochSampler(
        tiny_graph(np.random.default_rng(7), v_num=60, e_num=400)[0],
        np.arange(60), 16, [3], seed=5, workers=0,
    )
    pipe = SamplePipeline(src, range(2), depth=2, transfer=lambda b: b)
    with pytest.raises(SampleWorkerError, match="out of order"):
        list(pipe.epoch_stream(1))  # scheduled order starts at epoch 0
    pipe.close()


# ---- config / funnel ------------------------------------------------------


def test_sample_pipeline_key_validation(tmp_path, monkeypatch):
    cfg_path = tmp_path / "t.cfg"
    cfg_path.write_text(
        "ALGORITHM:GCNSAMPLESINGLE\nVERTICES:10\nSAMPLE_PIPELINE:pipelined\n"
    )
    cfg = InputInfo.read_from_cfg_file(str(cfg_path))
    assert cfg.sample_pipeline == "pipelined"
    cfg_path.write_text("SAMPLE_PIPELINE:tpipelined\n")
    with pytest.raises(ValueError, match="SAMPLE_PIPELINE"):
        InputInfo.read_from_cfg_file(str(cfg_path))

    # env override wins; set-but-empty is not an override
    monkeypatch.setenv("NTS_SAMPLE_PIPELINE", "device")
    assert resolve_sample_pipeline(cfg) == "device"
    monkeypatch.setenv("NTS_SAMPLE_PIPELINE", "")
    cfg.sample_pipeline = "pipelined"
    assert resolve_sample_pipeline(cfg) == "pipelined"
    monkeypatch.setenv("NTS_SAMPLE_PIPELINE", "bogus")
    with pytest.raises(ValueError, match="NTS_SAMPLE_PIPELINE"):
        resolve_sample_pipeline(cfg)


def test_non_sampled_trainer_refuses_pipeline(monkeypatch):
    """The lifecycle-funnel loudness rule: a trainer whose run loop would
    silently ignore SAMPLE_PIPELINE must refuse it."""
    from tests.test_models import _planted_cfg, _planted_data

    from neutronstarlite_tpu.models.gcn import GCNTrainer

    cfg = _planted_cfg(epochs=1)
    cfg.sample_pipeline = "pipelined"
    src, dst, datum = _planted_data(seed=3)
    with pytest.raises(ValueError, match="SAMPLE_PIPELINE"):
        GCNTrainer.from_arrays(cfg, src, dst, datum)


# ---- resilience -----------------------------------------------------------


def test_supervised_run_rolls_through_worker_fault(monkeypatch):
    """An injected worker death (exc@point=sample_produce) must surface as
    a sample_worker fault and the supervisor must retry to completion —
    with no leaked producer thread from the failed attempt."""
    from neutronstarlite_tpu.resilience import faults
    from neutronstarlite_tpu.resilience.supervisor import supervised_run

    monkeypatch.setenv("NTS_SAMPLE_WORKERS", "0")
    monkeypatch.setenv("NTS_FINAL_EVAL", "0")
    monkeypatch.setenv("NTS_FAULT_SPEC", "exc@point=sample_produce,epoch=1")
    monkeypatch.setenv("NTS_BACKOFF_BASE_S", "0.01")
    faults.reset()
    try:
        cfg, src, dst, datum, host_graph = _planted(seed=6)
        cfg.sample_pipeline = "pipelined"
        tr = GCNSampleTrainer.from_arrays(
            cfg, src, dst, datum, seed=0, host_graph=host_graph
        )
        result = supervised_run(tr)
        assert len(tr.loss_history) == cfg.epochs
        assert np.isfinite(result["loss"])
        snap = tr.metrics.snapshot()
        assert snap["counters"].get("resilience.restarts") == 1
    finally:
        faults.reset()
    assert _no_pipeline_threads()


# ---- device sampler -------------------------------------------------------


def test_device_sampler_exact_when_fanout_covers_degree(rng):
    """deg <= fanout must return EVERY in-neighbor (multiset-exactly what
    the host sampler returns there)."""
    g, _ = tiny_graph(rng, v_num=50, e_num=200)
    ds = DeviceUniformSampler.from_host(g)
    fan = int(g.in_degree.max())
    dsts = np.arange(50)
    src, dst_idx = ds.sample_neighbors(
        dsts, fan, np.random.default_rng(0), cap=50
    )
    host = Sampler(g, dsts, 50, [fan], rng=np.random.default_rng(1))
    hsrc, hdst = host._sample_neighbors(dsts, fan)
    for v in range(50):
        got = sorted(src[dst_idx == v].tolist())
        want = sorted(hsrc[hdst == v].tolist())
        assert got == want, f"dst {v}: {got} vs {want}"


def test_device_sampler_distribution_parity(rng):
    """Per-neighbor inclusion frequency must match the host sampler's
    (uniform without replacement) within a statistical tolerance."""
    g, _ = tiny_graph(rng, v_num=80, e_num=900)
    ds = DeviceUniformSampler.from_host(g)
    dstv = int(np.argmax(g.in_degree))
    deg = int(g.in_degree[dstv])
    fan = 3
    assert deg > 2 * fan  # the draw is a real subset
    host = Sampler(g, np.array([dstv]), 1, [fan],
                   rng=np.random.default_rng(11))
    dev_rng = np.random.default_rng(12)
    trials = 1500
    hc, dc = collections.Counter(), collections.Counter()
    for _ in range(trials):
        hsrc, _ = host._sample_neighbors(np.array([dstv]), fan)
        hc.update(hsrc.tolist())
        dsrc, _ = ds.sample_neighbors(
            np.array([dstv]), fan, dev_rng, cap=1
        )
        assert len(dsrc) == fan
        dc.update(dsrc.tolist())
    assert set(dc) == set(hc)  # same support (every neighbor reachable)
    # each neighbor's inclusion count is Binomial(trials, ~fan*mult/deg);
    # compare the two samplers' empirical frequencies loosely
    for v in set(hc):
        hf, df = hc[v] / trials, dc[v] / trials
        assert abs(hf - df) < 0.08, (v, hf, df)


def test_device_sampler_thinning_cap(rng):
    """Vertices past the width cap are pre-thinned at build: draws stay
    valid in-neighbors and the thinned count is reported."""
    g, _ = tiny_graph(rng, v_num=40, e_num=600)
    ds = DeviceUniformSampler.from_host(g, max_width=4)
    assert ds.thinned > 0 and ds.width == 4
    src, dst_idx = ds.sample_neighbors(
        np.arange(40), 3, np.random.default_rng(2), cap=40
    )
    edge_set = set(zip(g.row_indices.tolist(), g.dst_of_edge.tolist()))
    for u, v in zip(src.tolist(), dst_idx.tolist()):
        assert (u, v) in edge_set


def test_device_mode_trains(monkeypatch):
    """SAMPLE_PIPELINE:device end to end: the trainer runs, losses are
    finite and decrease (distribution-equivalent sampling), and the batch
    stream is deterministic per seed (two runs agree bitwise)."""
    monkeypatch.setenv("NTS_SAMPLE_WORKERS", "0")
    monkeypatch.setenv("NTS_FINAL_EVAL", "0")
    cfg, src, dst, datum, host_graph = _planted(seed=9)
    cfg.sample_pipeline = "device"

    def run():
        tr = GCNSampleTrainer.from_arrays(
            cfg, src, dst, datum, seed=0, host_graph=host_graph
        )
        tr.run()
        return tr.loss_history

    a = run()
    b = run()
    assert a == b  # per-seed deterministic
    assert all(np.isfinite(v) for v in a)
    assert a[-1] < a[0]
    assert _no_pipeline_threads()


# ---- telemetry ------------------------------------------------------------


def test_pipeline_stream_telemetry(tmp_path, monkeypatch):
    """A pipelined run's obs stream carries the sample.* counters/gauges,
    the per-batch cat=sample spans, the per-epoch stage attribution the
    other trainer families already have — all schema-valid — and the
    derived #sample_pipeline timeline line renders."""
    import json

    monkeypatch.setenv("NTS_SAMPLE_WORKERS", "0")
    monkeypatch.setenv("NTS_FINAL_EVAL", "0")
    monkeypatch.setenv("NTS_METRICS_DIR", str(tmp_path))
    from neutronstarlite_tpu.obs import schema

    cfg, src, dst, datum, host_graph = _planted(seed=5)
    cfg.sample_pipeline = "pipelined"
    tr = GCNSampleTrainer.from_arrays(
        cfg, src, dst, datum, seed=0, host_graph=host_graph
    )
    tr.run()
    path = tr.metrics.path
    events = []
    with open(path, encoding="utf-8") as fh:
        for line in fh:
            if line.strip():
                e = json.loads(line)
                schema.validate_event(e)
                events.append(e)
    summary = [e for e in events if e["event"] == "run_summary"][-1]
    counters = summary["counters"]
    assert counters["sample.produced"] > 0
    assert "sample.stall_ms" in counters and "sample.h2d_ms" in counters
    assert summary["gauges"]["sample.queue_depth"] >= 1
    spans = [e for e in events if e["event"] == "span"]
    names = {s["name"] for s in spans}
    assert {"sample_produce", "h2d_copy", "sample_wait"} <= names
    assert all(
        s["cat"] == "sample" for s in spans if s["name"] == "sample_produce"
    )
    # the stage attribution on the sampled family: live step_dispatch /
    # step_device spans under each epoch; the stall inside the batch loop
    # rides the epoch event as stages.sample_wait (its cat=sample spans
    # above are the per-batch truth)
    stage_names = {s["name"] for s in spans if s["cat"] == "stage"}
    assert {"step_dispatch", "step_device"} <= stage_names
    epochs = [e for e in events if e["event"] == "epoch"]
    assert epochs and all(
        0.0 <= e["stages"]["sample_wait"] <= e["stages"]["step_dispatch"]
        for e in epochs
    )

    from neutronstarlite_tpu.tools.trace_timeline import (
        sample_pipeline_report,
        timeline_block,
    )

    rep = sample_pipeline_report(events)
    assert rep is not None and rep["batches"] == counters["sample.produced"]
    assert any("#sample_pipeline=" in ln for ln in timeline_block(events))

    from neutronstarlite_tpu.tools.metrics_report import render_sample

    lines = render_sample(
        {"gauges": summary["gauges"], "counters": counters}
    )
    assert any("#sample_stall=" in ln for ln in lines)


def test_serve_pipelined_flush(tmp_path, monkeypatch):
    """Two-stage serving flush: train a tiny checkpoint, serve with
    SAMPLE_PIPELINE:pipelined — all requests answered, no errors, and the
    serve_summary carries the sample.* pipeline telemetry."""
    import json

    monkeypatch.setenv("NTS_SAMPLE_WORKERS", "0")
    monkeypatch.setenv("NTS_METRICS_DIR", str(tmp_path / "m"))
    from neutronstarlite_tpu.obs import schema
    from neutronstarlite_tpu.serve.engine import InferenceEngine
    from neutronstarlite_tpu.serve.server import InferenceServer

    cfg, src, dst, datum, host_graph = _planted(seed=8)
    cfg.epochs = 1
    cfg.checkpoint_dir = str(tmp_path / "ckpt")
    cfg.serve_max_batch = 8
    cfg.serve_buckets = "2-8"
    cfg.serve_max_wait_ms = 2.0
    cfg.sample_pipeline = "pipelined"
    tr = GCNSampleTrainer.from_arrays(
        cfg, src, dst, datum, seed=0, host_graph=host_graph
    )
    tr.run()

    engine = InferenceEngine(tr, cfg.checkpoint_dir,
                             rng=np.random.default_rng(0))
    engine.warmup()
    server = InferenceServer(engine)
    assert server.pipelined
    rng = np.random.default_rng(3)
    pending = [server.submit(rng.integers(0, cfg.vertices, 2))
               for _ in range(25)]
    for req in pending:
        out = req.result(timeout=60.0)
        assert out.shape == (2, 3) and np.isfinite(out).all()
    stats = server.close()
    assert stats["requests"] == 25 and stats["shed"] == 0

    events = []
    with open(engine.metrics.path, encoding="utf-8") as fh:
        for line in fh:
            if line.strip():
                e = json.loads(line)
                schema.validate_event(e)
                events.append(e)
    summary = [e for e in events if e["event"] == "serve_summary"][-1]
    assert "gauges" in summary
    names = {e["name"] for e in events if e["event"] == "span"}
    # producer stages + executor stages, all joined by flush_id
    assert {"sample", "h2d_copy", "execute", "reply", "batch_flush"} <= names
    # the executor thread is gone after close
    assert not [
        t for t in threading.enumerate()
        if t.name == "serve-executor" and t.is_alive()
    ]
