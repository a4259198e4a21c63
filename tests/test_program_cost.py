"""Compiled-program cost attribution (obs/cost): capture paths, graceful
degradation, and the run_summary/ledger ride-along."""

from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import pytest

from neutronstarlite_tpu.obs import registry, schema
from neutronstarlite_tpu.obs.cost import (
    capture_program_cost,
    cost_from_analysis,
    memory_from_compiled,
)


@pytest.fixture(autouse=True)
def _force_capture(monkeypatch):
    """The default gate is AUTO (capture only with a sink/ledger); these
    unit tests exercise the capture machinery itself, so force it on
    (the gate has its own test below)."""
    monkeypatch.setenv("NTS_PROGRAM_COST", "1")
    yield


def _reg(tmp_path=None):
    return registry.MetricsRegistry(
        "cost-test-1", algorithm="T", fingerprint="f",
        path=str(tmp_path / "s.jsonl") if tmp_path is not None else None,
    )


def _matmul():
    return jax.jit(lambda x: (x @ x).sum()), (jnp.ones((32, 32)),)


# ---- capture paths ----------------------------------------------------------


def test_capture_from_jitted_lowering_no_compile(tmp_path):
    """The default trainer path: cost from the lowering alone (flops +
    bytes, memory null — no second compile)."""
    reg = _reg(tmp_path)
    fn, args = _matmul()
    rec = capture_program_cost(reg, "test.matmul", jitted=fn, args=args)
    assert rec["available"] is True
    assert rec["source"] == "lowered"
    assert rec["flops"] > 0 and rec["bytes_accessed"] > 0
    assert rec["memory"] is None
    assert rec["custom_calls"] == []  # plain XLA: no kernel call
    reg.close()
    events = [json.loads(l) for l in open(tmp_path / "s.jsonl")
              if l.strip()]
    assert schema.validate_stream(events) == len(events)
    assert events[-1]["event"] == "program_cost"
    assert events[-1]["label"] == "test.matmul"


def test_lowering_capture_names_custom_calls():
    """A lowered module's custom-call targets ride the record: the way a
    run shows its Pallas kernel lowered as a Mosaic call (chip_smoke's
    train_pallas leg reads ``tpu_custom_call`` here) and was not
    interpreted. On the CPU rig LAPACK's QR is the custom call at hand."""
    reg = _reg()
    rec = capture_program_cost(
        reg, "test.qr", jitted=jax.jit(jnp.linalg.qr),
        args=(jnp.ones((8, 8)),),
    )
    assert any(c.startswith("lapack") for c in rec["custom_calls"]), rec
    schema.validate_event(rec)
    with pytest.raises(ValueError):
        schema.validate_event(dict(rec, custom_calls=["ok", ""]))


def test_capture_from_compiled_includes_memory():
    """The serve-AOT path: an existing Compiled yields cost AND the
    buffer-allocation memory analysis for free."""
    reg = _reg()
    fn, args = _matmul()
    compiled = fn.lower(*args).compile()
    rec = capture_program_cost(reg, "serve.bucket_4", compiled=compiled)
    assert rec["available"] is True
    assert rec["source"] == "compiled"
    assert rec["flops"] > 0
    mem = rec["memory"]
    assert mem is not None
    assert mem["argument_bytes"] == 32 * 32 * 4
    assert mem["output_bytes"] == 4
    assert mem["peak_bytes"] >= mem["argument_bytes"] + mem["output_bytes"]


def test_nts_cost_memory_compiles_the_lowering(monkeypatch):
    monkeypatch.setenv("NTS_COST_MEMORY", "1")
    reg = _reg()
    fn, args = _matmul()
    rec = capture_program_cost(reg, "test.mem", jitted=fn, args=args)
    assert rec["source"] == "compiled"
    assert rec["memory"] is not None


def test_degraded_backend_leaves_warning_record_not_crash():
    """cost_analysis AND memory_analysis both raising must still leave a
    schema-valid available=false record — queryable absence, never
    silence, never a crash."""

    class Broken:
        def cost_analysis(self):
            raise RuntimeError("backend exposes no cost analysis")

        def memory_analysis(self):
            raise NotImplementedError

    reg = _reg()
    rec = capture_program_cost(reg, "broken.program", compiled=Broken())
    assert rec["available"] is False
    assert "cost_analysis" in rec["error"]
    schema.validate_event(rec)


def test_lowering_failure_leaves_error_record():
    class NotJitted:
        def lower(self, *a):
            raise TypeError("not a jitted function")

    reg = _reg()
    rec = capture_program_cost(reg, "bad.lower", jitted=NotJitted(),
                               args=())
    assert rec["available"] is False and rec["source"] == "error"
    assert "not a jitted function" in rec["error"]


def test_kill_switch_disables_capture(monkeypatch):
    monkeypatch.setenv("NTS_PROGRAM_COST", "0")
    reg = _reg()
    fn, args = _matmul()
    assert capture_program_cost(reg, "off", jitted=fn, args=args) is None
    assert reg.program_costs == []


def test_auto_gate_requires_a_persistence_surface(tmp_path, monkeypatch):
    """Unset NTS_PROGRAM_COST = AUTO: a sink-less registry skips capture
    (the lowering's XLA cost pass must not tax every bare trainer build
    in the suite); a registry with a JSONL sink — or an armed ledger —
    captures."""
    monkeypatch.delenv("NTS_PROGRAM_COST", raising=False)
    monkeypatch.delenv("NTS_LEDGER_DIR", raising=False)
    fn, args = _matmul()
    assert capture_program_cost(_reg(), "auto.skip", jitted=fn,
                                args=args) is None
    rec = capture_program_cost(_reg(tmp_path), "auto.sink", jitted=fn,
                               args=args)
    assert rec is not None and rec["available"] is True
    monkeypatch.setenv("NTS_LEDGER_DIR", str(tmp_path))
    rec = capture_program_cost(_reg(), "auto.ledger", jitted=fn,
                               args=args)
    assert rec is not None and rec["available"] is True


# ---- helpers ----------------------------------------------------------------


def test_cost_from_analysis_accepts_both_shapes():
    d = {"flops": 10.0, "bytes accessed": 20.0}
    assert cost_from_analysis(d)["flops"] == 10.0
    assert cost_from_analysis([d])["bytes_accessed"] == 20.0
    assert cost_from_analysis(None)["flops"] is None


def test_memory_from_compiled_none_when_absent():
    class NoMem:
        def memory_analysis(self):
            return None

    assert memory_from_compiled(NoMem()) is None


# ---- consolidation ----------------------------------------------------------


def test_program_costs_ride_run_summary_and_ledger_row(tmp_path,
                                                       monkeypatch):
    from neutronstarlite_tpu.obs import ledger

    reg = _reg()
    fn, args = _matmul()
    capture_program_cost(reg, "a.step", jitted=fn, args=args)
    capture_program_cost(reg, "b.step", jitted=fn, args=args)
    summ = reg.run_summary(
        epochs=1, avg_epoch_s=0.1, phases={},
        epoch_time={"first_s": 0.1, "warm_median_s": None,
                    "compile_overhead_s": None},
        memory={"available": False, "bytes_in_use": None,
                "peak_bytes_in_use": None, "devices": []},
    )
    labels = [c["label"] for c in summ["program_costs"]]
    assert labels == ["a.step", "b.step"]
    row = ledger.run_row(summ, graph_digest="g")
    assert [c["label"] for c in row["program_costs"]] == labels
    assert row["kind"] == "run"


def test_report_renders_program_cost_block(tmp_path, capsys):
    reg = _reg(tmp_path)
    fn, args = _matmul()
    capture_program_cost(reg, "fullbatch.train_step/T", jitted=fn,
                         args=args)
    reg.event("epoch", epoch=0, seconds=0.5, loss=1.0)
    reg.close()
    from neutronstarlite_tpu.tools.metrics_report import main as report_main

    rc = report_main([str(tmp_path / "s.jsonl")])
    out = capsys.readouterr().out
    assert rc == 0
    assert "program costs:" in out
    assert "#program_cost=fullbatch.train_step/T" in out
    assert "flops=" in out


def test_capture_without_registry_is_noop():
    fn, args = _matmul()
    assert capture_program_cost(None, "x", jitted=fn, args=args) is None
