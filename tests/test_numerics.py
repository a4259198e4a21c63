"""Numerics health plane (ISSUE 15): on-device tensor-stat telemetry,
non-finite provenance, and measured wire quantization error.

The contracts pinned here:

- ``NTS_NUMERICS`` off leaves the default step program BYTE-IDENTICAL
  (jaxpr string equality against an untouched build) and carries no
  ``is_finite`` primitive; the stats variant is a second program whose
  extra output changes no training math (bitwise loss-curve parity).
- The chaos oracle: ``nan_loss@layer=k`` injection under supervision
  yields a ``nonfinite_provenance`` record naming layer k EXACTLY, for
  k in {0, 1}, on the fullbatch AND gcn_dist families — and the run
  still recovers (the acceptance criterion).
- ``guards.nonfinite_leaves`` does ONE host fetch for the whole tree
  (the per-leaf round-trip regression this PR fixes).
- The measured bf16 wire quantization error matches a host-side exact
  computation within 1e-6, and an artificially large error flags the
  matching tune-cache entry for re-trial (the drift-audit numerics leg).
"""

from __future__ import annotations

import glob
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from neutronstarlite_tpu.graph.storage import build_graph
from neutronstarlite_tpu.models.gcn import GCNTrainer
from neutronstarlite_tpu.models.gcn_dist import DistGCNTrainer
from neutronstarlite_tpu.obs import numerics, registry
from neutronstarlite_tpu.obs.flight import FlightRecorder, reset_dump_budget
from neutronstarlite_tpu.obs.schema import validate_stream
from neutronstarlite_tpu.resilience import faults, guards
from neutronstarlite_tpu.resilience.faults import parse_fault_spec
from neutronstarlite_tpu.resilience.supervisor import supervised_run
from neutronstarlite_tpu.utils.config import InputInfo
from tests.test_models import _planted_cfg, _planted_data


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    for var in ("NTS_FAULT_SPEC", "NTS_NUMERICS", "NTS_NUMERICS_EVERY",
                "NTS_QUANT_PROBE", "NTS_QUANT_TOL", "NTS_METRICS_DIR",
                "NTS_WIRE_DTYPE"):
        monkeypatch.delenv(var, raising=False)
    monkeypatch.setenv("NTS_BACKOFF_BASE_S", "0")
    faults.reset()
    yield
    faults.reset()


def _stream(metrics_dir):
    evs = []
    for f in sorted(glob.glob(os.path.join(str(metrics_dir), "*.jsonl"))):
        with open(f) as fh:
            evs.extend(json.loads(line) for line in fh if line.strip())
    validate_stream(evs)
    return evs


def _of(evs, kind):
    return [e for e in evs if e["event"] == kind]


def _fullbatch(epochs=3, seed=0, host_graph=None):
    cfg = _planted_cfg(v_num=120, classes=3, f=8, epochs=epochs)
    cfg.layer_string = "8-8-3"
    src, dst, datum = _planted_data(v_num=120, classes=3, f=8, seed=1)
    if host_graph is None:
        host_graph = build_graph(src, dst, 120, weight="gcn_norm")
    return GCNTrainer.from_arrays(cfg, src, dst, datum, seed=seed,
                                  host_graph=host_graph), host_graph


def _dist_sim(epochs=3, partitions=2, wire_dtype="", host_graph=None):
    cfg = InputInfo()
    cfg.algorithm = "GCNDIST"
    cfg.vertices = 120
    cfg.layer_string = "8-8-3"
    cfg.epochs = epochs
    cfg.learn_rate = 0.01
    cfg.weight_decay = 1e-4
    cfg.decay_epoch = -1
    cfg.drop_rate = 0.0
    cfg.partitions = partitions
    cfg.dist_path = "ring_blocked_sim"
    cfg.kernel_tile = 16
    cfg.wire_dtype = wire_dtype
    src, dst, datum = _planted_data(v_num=120, classes=3, f=8, seed=1)
    if host_graph is None:
        host_graph = build_graph(src, dst, 120, weight="gcn_norm")
    return DistGCNTrainer.from_arrays(cfg, src, dst, datum,
                                      host_graph=host_graph), host_graph


# ---- batched non-finite leaf check (satellite 1) ----------------------------


def test_nonfinite_leaves_one_fetch_for_whole_tree(monkeypatch):
    """The whole-tree check must do exactly ONE host fetch however many
    leaves the tree has — the per-leaf round trip is the regression."""
    tree = {
        "a": jnp.ones((4, 4)),
        "b": [jnp.zeros(3), jnp.array([1.0, float("nan")])],
        "c": jnp.arange(3),  # int leaf: skipped like before
        "d": {"w": jnp.full((2, 2), 2.0), "x": jnp.array([np.inf])},
    }
    calls = []
    real = numerics._fetch
    monkeypatch.setattr(
        numerics, "_fetch", lambda x: (calls.append(1), real(x))[1]
    )
    bad = guards.nonfinite_leaves(tree)
    assert len(calls) == 1, f"expected 1 host fetch, saw {len(calls)}"
    assert len(bad) == 2
    assert any("'b'" in n for n in bad) and any("'x'" in n for n in bad)

    calls.clear()
    assert guards.nonfinite_leaves({"a": jnp.ones(5)}) == []
    assert len(calls) == 1
    # no floating leaves at all: nothing to fetch
    calls.clear()
    assert guards.nonfinite_leaves({"i": jnp.arange(4)}) == []
    assert len(calls) == 0


def test_finite_flags_reuses_one_compiled_reduce():
    """The jit wrapper must PERSIST across calls — a per-call closure
    would retrace+recompile every guarded epoch, inverting the
    one-fetch optimization into a per-epoch XLA compile."""
    numerics._finite_flags_jit = None
    tree = {"a": jnp.ones((3, 3)), "b": jnp.zeros(5)}
    guards.nonfinite_leaves(tree)
    wrapper = numerics._finite_flags_jit
    assert wrapper is not None
    for _ in range(3):
        guards.nonfinite_leaves(tree)
    assert numerics._finite_flags_jit is wrapper
    if hasattr(wrapper, "_cache_size"):
        assert wrapper._cache_size() == 1


# ---- NTS_NUMERICS off: untouched program (overhead pin) ---------------------


def _jaxpr_text(fn, args) -> str:
    """The jaxpr string with function-object addresses normalized away
    (`<function f at 0x7f..>` reprs embed the process's heap layout —
    the PROGRAM must be byte-identical, the addresses cannot be)."""
    import re

    return re.sub(r"0x[0-9a-f]+", "0xADDR", str(jax.make_jaxpr(fn)(*args)))


def test_numerics_off_step_program_byte_identical(monkeypatch):
    """With numerics off the step jaxpr must be BYTE-IDENTICAL to an
    untouched build and hold no is_finite primitive; the stats variant
    is a separate program that does."""
    t_off, g = _fullbatch()
    assert t_off._train_step_stats is None
    jaxpr_off = _jaxpr_text(t_off._train_step, t_off.aot_args())
    assert "is_finite" not in jaxpr_off

    monkeypatch.setenv("NTS_NUMERICS", "1")
    t_on, _ = _fullbatch(host_graph=g)
    assert t_on._train_step_stats is not None
    jaxpr_default = _jaxpr_text(t_on._train_step, t_on.aot_args())
    assert jaxpr_default == jaxpr_off, (
        "NTS_NUMERICS=1 must not touch the DEFAULT step program"
    )
    jaxpr_stats = _jaxpr_text(t_on._train_step_stats, t_on.aot_args())
    assert "is_finite" in jaxpr_stats


def test_numerics_on_bitwise_loss_parity(monkeypatch, tmp_path):
    """The stats output is a pure extra output: loss curves with
    numerics on and off must match bitwise; the on-stream carries
    per-layer tensor_stats and numerics gauges."""
    t_off, g = _fullbatch(epochs=4)
    r_off = t_off.run()

    monkeypatch.setenv("NTS_NUMERICS", "1")
    monkeypatch.setenv("NTS_METRICS_DIR", str(tmp_path))
    t_on, _ = _fullbatch(epochs=4, host_graph=g)
    r_on = t_on.run()

    assert t_on.loss_history == t_off.loss_history
    assert r_on["loss"] == r_off["loss"]
    evs = _stream(tmp_path)
    stats = _of(evs, "tensor_stats")
    names = {e["name"] for e in stats}
    for want in ("params/l0", "params/l1", "grads/l0", "acts/l0",
                 "acts/l1", "logits", "grads/global"):
        assert want in names, f"missing tensor_stats group {want}"
    assert all(e["finite_fraction"] == 1.0 for e in stats)
    summ = _of(evs, "run_summary")[-1]
    assert summ["gauges"]["numerics.finite_fraction_min"] == 1.0
    assert summ["gauges"]["numerics.grad_global_norm"] > 0


def test_numerics_every_gates_the_fetch(monkeypatch, tmp_path):
    monkeypatch.setenv("NTS_NUMERICS", "1")
    monkeypatch.setenv("NTS_NUMERICS_EVERY", "2")
    monkeypatch.setenv("NTS_METRICS_DIR", str(tmp_path))
    t, _ = _fullbatch(epochs=4)
    t.run()
    epochs = {e["epoch"] for e in _of(_stream(tmp_path), "tensor_stats")}
    assert epochs == {0, 2}


def test_finite_fraction_exact_at_scale():
    """One NaN in a >2^24-element tensor must read < 1.0: the tallies
    stay integer and the fraction divides in f64 host-side (an in-jit
    f32 fraction rounds it back to exactly 1.0 — the silent-blindness
    regression the review caught)."""
    n = 2 ** 24 + 4
    x = np.ones(n, dtype=np.float32)
    x[123] = np.nan
    st = jax.device_get(jax.jit(
        lambda a: numerics.group_stats([a])
    )(jnp.asarray(x)))
    fields = numerics._stat_fields(st)
    assert fields["finite_fraction"] < 1.0
    assert int(st["nonfinite_count"]) == 1
    assert fields["zero_fraction"] == 0.0


def test_stale_layer_poison_never_leaks():
    """A pending nan_loss@layer=k poison must be consumed by EVERY exit
    path — an unarmed run's warning branch and capture_provenance's
    early returns — or the next organic fault's replay would be falsely
    poisoned and marked injected."""
    import os as _os

    _os.environ["NTS_FAULT_SPEC"] = "nan_loss@epoch=0,layer=1"
    try:
        faults.fault_point("epoch_loss", epoch=0, value=1.0)
        assert faults.pending_layer_poison() == 1

        class T:  # minimal unarmed toolkit
            pass

        guards.epoch_check(T(), 0, 0.1, float("nan"))  # unarmed: warns
        assert faults.pending_layer_poison() is None
    finally:
        del _os.environ["NTS_FAULT_SPEC"]
        faults.reset()

    # capture_provenance's one-shot early return also consumes it
    t, _ = _fullbatch(epochs=1)
    t._nonfinite_replayed = True
    faults._layer_poison = 1
    assert numerics.capture_provenance(t, 0, "nonfinite_loss") is None
    assert faults.pending_layer_poison() is None


# ---- chaos oracle: nan_loss@layer=k -> provenance names layer k -------------


def test_nan_loss_layer_arg_parses():
    spec = parse_fault_spec("nan_loss@epoch=1,layer=2")[0]
    assert spec.layer == 2 and spec.epoch == 1
    with pytest.raises(ValueError, match="bad fault arg"):
        parse_fault_spec("nan_loss@layer=two")


@pytest.mark.parametrize("layer", [0, 1])
def test_provenance_names_injected_layer_fullbatch(layer, monkeypatch,
                                                   tmp_path):
    """The acceptance chaos oracle, fullbatch family: injected at layer
    k => nonfinite_provenance names layer k exactly, and the supervised
    run still recovers."""
    monkeypatch.setenv("NTS_METRICS_DIR", str(tmp_path))
    monkeypatch.setenv(
        "NTS_FAULT_SPEC", f"nan_loss@epoch=1,layer={layer}"
    )
    t, _ = _fullbatch(epochs=3)
    result = supervised_run(t)
    assert np.isfinite(result["loss"])
    evs = _stream(tmp_path)
    prov = _of(evs, "nonfinite_provenance")
    assert len(prov) == 1
    assert prov[0]["layer"] == layer
    assert prov[0]["op"] == "activation"
    assert prov[0]["injected"] is True
    assert prov[0]["fault_kind"] == "nonfinite_loss"
    # the provenance record precedes its fault record in the stream
    fault = next(e for e in evs if e["event"] == "fault")
    assert prov[0]["seq"] < fault["seq"]


@pytest.mark.parametrize("layer", [0, 1])
def test_provenance_names_injected_layer_dist(layer, monkeypatch,
                                              tmp_path):
    """The acceptance chaos oracle, gcn_dist family (sim ring)."""
    monkeypatch.setenv("NTS_METRICS_DIR", str(tmp_path))
    monkeypatch.setenv(
        "NTS_FAULT_SPEC", f"nan_loss@epoch=1,layer={layer}"
    )
    t, _ = _dist_sim(epochs=3)
    result = supervised_run(t)
    assert np.isfinite(result["loss"])
    prov = _of(_stream(tmp_path), "nonfinite_provenance")
    assert len(prov) == 1
    assert prov[0]["layer"] == layer
    assert prov[0]["op"] == "activation"
    assert prov[0]["injected"] is True


def test_provenance_attributes_poisoned_params(tmp_path, monkeypatch):
    """A genuinely non-finite parameter layer: the walk checks params
    FIRST, so the verdict is op=params at the poisoned layer."""
    monkeypatch.setenv("NTS_METRICS_DIR", str(tmp_path))
    t, _ = _fullbatch(epochs=1)
    w = np.asarray(t.params[1]["W"]).copy()
    w[0, 0] = np.nan
    t.params[1]["W"] = jnp.asarray(w)
    rec = numerics.capture_provenance(t, 0, "nonfinite_params")
    assert rec["layer"] == 1 and rec["op"] == "params"
    assert rec["injected"] is False
    # one-shot: the second call must not replay again
    assert numerics.capture_provenance(t, 0, "nonfinite_params") is None


def test_provenance_degrades_without_replay_hook(tmp_path, monkeypatch):
    """A trainer without a replay hook still leaves an (unattributed)
    record instead of nothing."""
    monkeypatch.setenv("NTS_METRICS_DIR", str(tmp_path))
    t, _ = _fullbatch(epochs=1)
    t.numerics_replay = lambda epoch: None
    rec = numerics.capture_provenance(t, 0, "nonfinite_loss")
    assert rec["layer"] is None and rec["fault_kind"] == "nonfinite_loss"
    validate_stream([rec])


# ---- wire quantization error ------------------------------------------------


def test_quant_rel_err_matches_host_exact():
    """The acceptance parity oracle: the jitted measurement equals a
    host-side exact computation within 1e-6."""
    import ml_dtypes

    rng = np.random.default_rng(7)
    x = (rng.standard_normal((257, 33)) * 3.0).astype(np.float32)
    measured = float(jax.jit(
        lambda a: numerics.quant_rel_err(a, jnp.bfloat16)
    )(jnp.asarray(x)))
    xq = x.astype(ml_dtypes.bfloat16).astype(np.float32)
    exact = float(
        np.sqrt(np.mean((xq - x) ** 2)) / np.sqrt(np.mean(x ** 2))
    )
    assert abs(measured - exact) <= 1e-6
    assert 0 < measured < 0.01  # bf16's ~4e-3 per-element RMS regime


def test_quant_probe_emits_gauge_and_record(monkeypatch, tmp_path):
    monkeypatch.setenv("NTS_QUANT_PROBE", "1")
    monkeypatch.setenv("NTS_METRICS_DIR", str(tmp_path))
    t, _ = _dist_sim(epochs=2, wire_dtype="bf16")
    t.run()
    evs = _stream(tmp_path)
    payloads = [e for e in _of(evs, "tensor_stats")
                if e["name"] == "wire.payload/l0"]
    assert len(payloads) == 2  # one per epoch
    err = payloads[-1]["quant_rel_err"]
    assert err is not None and err > 0
    summ = _of(evs, "run_summary")[-1]
    assert summ["gauges"]["wire.quant_rel_err"] == err

    import ml_dtypes

    # the layer-0 payload is the padded feature slab, which rides the
    # ring once (the input_aggregate phase, where the probe reads it);
    # t.feature_p is its aggregate
    x = t.dist.pad_vertex_array(t.datum.feature)
    xq = x.astype(ml_dtypes.bfloat16).astype(np.float32)
    exact = float(
        np.sqrt(np.mean((xq - x) ** 2)) / np.sqrt(np.mean(x ** 2))
    )
    assert abs(err - exact) <= 1e-6


def test_quant_drift_flags_matching_tune_entry(tmp_path):
    """The drift-audit numerics leg e2e: a bf16 tune decision whose
    measured quant error exceeds NTS_QUANT_TOL gets EXACTLY its cache
    entry flagged for re-trial; the CLI exits 3."""
    from neutronstarlite_tpu.tools import drift_audit
    from neutronstarlite_tpu.tune import cache

    tune_dir = tmp_path / "tune"
    key = cache.CacheKey(
        graph_digest="g1", family="dist_dense/DistGCNTrainer",
        partitions=2, layers="8-8-3", backend="b1",
    )
    path = cache.store(
        key,
        {"candidate": "ring_blocked|-|-|-|bf16", "wire_dtype": "bf16"},
        directory=str(tune_dir),
    )
    other = cache.CacheKey(
        graph_digest="g2", family="dist_dense/DistGCNTrainer",
        partitions=2, layers="8-8-3", backend="b1",
    )
    other_path = cache.store(
        other,
        {"candidate": "ring_blocked|-|-|-|bf16", "wire_dtype": "bf16"},
        directory=str(tune_dir),
    )

    stream_dir = tmp_path / "obs"
    os.makedirs(stream_dir)
    reg = registry.MetricsRegistry(
        "r1", algorithm="GCNDIST", fingerprint="f",
        path=str(stream_dir / "s.jsonl"),
    )
    reg.event(
        "tune_decision", family=key.family,
        candidate="ring_blocked|-|-|-|bf16", source="measured",
        partitions=2, seconds=0.01, decision={"wire_dtype": "bf16"},
        graph_digest=key.graph_digest, backend=key.backend,
        layers=key.layers,
    )
    reg.event(
        "tensor_stats", name="wire/l0", epoch=0, finite_fraction=1.0,
        absmax=1.0, rms=0.5, zero_fraction=0.0, quant_rel_err=0.5,
    )
    reg.close()

    rc = drift_audit.main([
        str(stream_dir), "--tune-dir", str(tune_dir), "--json",
    ])
    assert rc == 3
    entry = json.load(open(path))
    assert entry.get("drift_flag"), "implicated entry was not flagged"
    assert "quant" in entry["drift_flag"]["reason"]
    assert not json.load(open(other_path)).get("drift_flag"), (
        "a different graph's entry must not be flagged"
    )


def test_quant_within_tol_does_not_drift():
    from neutronstarlite_tpu.tools import drift_audit

    events = [{
        "event": "tensor_stats", "run_id": "r", "schema": 1, "ts": 0.0,
        "seq": 0, "name": "wire/l0", "finite_fraction": 1.0,
        "absmax": 1.0, "rms": 0.5, "zero_fraction": 0.0,
        "quant_rel_err": 0.002,
    }]
    assert drift_audit.wire_quant_drift(events, 0.01) == []
    drifts = drift_audit.wire_quant_drift(events, 0.001)
    assert len(drifts) == 1 and drifts[0]["source"] == "wire_quant"
    # no tuner decision in the stream: nothing to flag, never a crash
    assert drift_audit.flag_tune_cache(drifts, "/nonexistent") == []
    # NTS_QUANT_TOL=0 = "flag ANY measured error": the drift is the raw
    # error, never a ZeroDivisionError
    zero = drift_audit.wire_quant_drift(events, 0.0)
    assert len(zero) == 1 and zero[0]["drift"] == 0.002


# ---- serve engine batch stats -----------------------------------------------


def test_serve_batch_stats_loud_only_when_nonfinite(tmp_path):
    reg = registry.MetricsRegistry(
        "s", algorithm="SERVE", fingerprint="f",
        path=str(tmp_path / "s.jsonl"),
    )
    numerics.observe_serve_batch(reg, np.array([[1.0, 2.0]]), 4)
    assert reg.counter_get("numerics.serve_nonfinite_batches") == 0
    numerics.observe_serve_batch(reg, np.array([[1.0, np.nan]]), 4)
    assert reg.counter_get("numerics.serve_nonfinite_batches") == 1
    reg.close()
    evs = [json.loads(l) for l in open(tmp_path / "s.jsonl") if l.strip()]
    validate_stream(evs)
    loud = _of(evs, "tensor_stats")
    assert len(loud) == 1  # only the non-finite batch left a record
    assert loud[0]["name"] == "serve/logits/bucket_4"
    assert loud[0]["finite_fraction"] == 0.5


# ---- flight pinning ---------------------------------------------------------


def test_pinned_stats_ride_dump_after_ring_rotation(tmp_path, monkeypatch):
    monkeypatch.setenv("NTS_FLIGHT_DIR", str(tmp_path / "fl"))
    reset_dump_budget()
    fr = FlightRecorder(capacity=16)
    pinned = {"event": "tensor_stats", "run_id": "r", "schema": 1,
              "ts": 1.0, "seq": 0, "name": "grads/global",
              "finite_fraction": 1.0, "absmax": 0.9, "rms": 0.9,
              "zero_fraction": 0.0}
    fr.record(pinned)
    fr.pin("tensor_stats/grads/global", pinned)
    for i in range(40):  # rotate the pinned record out of the ring
        fr.record({"event": "epoch", "run_id": "r", "schema": 1,
                   "ts": 2.0 + i, "seq": 1 + i, "epoch": i,
                   "seconds": 0.1, "loss": 1.0})
    path = fr.dump("test")
    evs = [json.loads(l) for l in open(path) if l.strip()]
    stats = _of(evs, "tensor_stats")
    assert len(stats) == 1 and stats[0]["name"] == "grads/global"
    validate_stream(evs)


# ---- report / diff / sentinel surfaces --------------------------------------


def test_diff_metrics_and_floors_cover_numerics():
    from neutronstarlite_tpu.tools.metrics_report import (
        _TOL_FLOORS,
        _diff_metrics,
    )

    rec = {
        "epoch_time": {}, "counters": {}, "epochs": 2,
        "gauges": {"numerics.grad_global_norm": 0.9,
                   "wire.quant_rel_err": 0.0016},
    }
    out = _diff_metrics(rec, None)
    assert out["grad_global_norm"] == 0.9
    assert out["wire_quant_rel_err"] == 0.0016
    assert _TOL_FLOORS["grad_global_norm"] >= 0.2
    assert 0 < _TOL_FLOORS["wire_quant_rel_err"] <= 0.1


def test_sentinel_grad_norm_advisory_two_sided():
    from neutronstarlite_tpu.tools.perf_sentinel import check

    def row(gn):
        return {"kind": "run", "cfg": "c", "graph_digest": "g",
                "backend": "b", "warm_median_epoch_s": 1.0,
                "grad_global_norm": gn}

    rows = [row(1.0), row(1.05), row(0.95), row(30.0)]
    out = check(rows, "run", k=5, min_baseline=2, nsigma=3.0,
                floor=0.08, max_tol=0.5)
    assert out.get("grad_norm_drift") is True
    assert any("grad_global_norm" in w for w in out["warnings"])
    # drift is ADVISORY: it never joins the regressed set
    assert "grad_global_norm" not in out["regressed"]

    calm = check(rows[:3] + [row(1.02)], "run", k=5, min_baseline=2,
                 nsigma=3.0, floor=0.08, max_tol=0.5)
    assert not calm.get("grad_norm_drift")


def test_numerics_ledger_row_fields():
    from neutronstarlite_tpu.obs.ledger import run_row

    summ = {
        "counters": {}, "epochs": 2, "epoch_time": {},
        "gauges": {"numerics.grad_global_norm": 0.7,
                   "wire.quant_rel_err": 0.002},
        "run_id": "r", "algorithm": "A", "fingerprint": "f",
    }
    row = run_row(summ, "digest")
    assert row["grad_global_norm"] == 0.7
    assert row["wire_quant_rel_err"] == 0.002
