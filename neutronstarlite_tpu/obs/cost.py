"""Compiled-program cost attribution: XLA's own numbers per executable.

Every measurement surface so far is OUTSIDE the executable: host epoch
timers, analytically priced wire counters, structural jaxpr pins ("no
[Ep, f] aval"). XLA itself knows more — ``Compiled.cost_analysis()``
(FLOPs, bytes accessed) and ``Compiled.memory_analysis()`` (argument /
output / temp / generated-code buffer allocation) — and it knows it ONCE,
at compile time, for exactly the program that will run. This module
captures that as one typed ``program_cost`` record per executable, keyed
by a stable program label, so the perf ledger (obs/ledger.py) and the
drift auditor (tools/drift_audit.py) get real per-executable numbers next
to the structural pins.

Two capture paths, cheapest that fits:

- ``compiled=``: an already-compiled ``jax.stages.Compiled`` (the serve
  engine's AOT bucket ladder, comm_bench legs) — cost AND memory
  analysis are free reads off the existing executable
  (``source="compiled"``).
- ``jitted=`` + ``args=``: a ``jax.jit`` function the caller will invoke
  through the normal cached-call path (train steps). Lowering is one
  extra trace but NO extra compile (``Lowered.cost_analysis()`` runs
  XLA's HLO cost pass on the unoptimized module), so the default capture
  never doubles a trainer's compile time (``source="lowered"``, memory
  null). ``NTS_COST_MEMORY=1`` opts into compiling the lowering too for
  the full memory analysis — the persistent compile cache makes that a
  near-free second hit where it is configured.

Degradation is graceful and LOUD-in-band: a backend that exposes neither
analysis (or a lowering that fails) still leaves a record —
``available=false`` with the error — never a crash and never silence
(the "probe that times out leaves no trace" postmortem, applied to cost
capture). ``NTS_PROGRAM_COST`` is three-state: ``0`` never, ``1``
always, unset = capture only when telemetry persists (a JSONL sink or an
armed ledger) — see :func:`cost_enabled` for why the auto gate exists.
"""

from __future__ import annotations

import os
import re
from typing import Any, Dict, List, Optional

from neutronstarlite_tpu.utils.logging import get_logger

log = get_logger("obs")

# memory_analysis attribute -> record field (plain ints; the host_* split
# is dropped — host staging buffers are not the HBM envelope this record
# exists to pin)
_MEM_FIELDS = {
    "argument_size_in_bytes": "argument_bytes",
    "output_size_in_bytes": "output_bytes",
    "temp_size_in_bytes": "temp_bytes",
    "alias_size_in_bytes": "alias_bytes",
    "generated_code_size_in_bytes": "generated_code_bytes",
}


def cost_enabled(metrics=None) -> bool:
    """Three-state ``NTS_PROGRAM_COST``: ``0`` = never, ``1`` = always,
    unset = AUTO — capture only when the telemetry is actually being
    persisted (the registry has a JSONL sink, or ``NTS_LEDGER_DIR`` is
    armed). The auto gate matters: ``Lowered.cost_analysis()`` runs an
    XLA pass over the traced module, which costs seconds per dist
    trainer build — fine inside an instrumented run, unacceptable as a
    tax on every bare construction in the test suite."""
    raw = os.environ.get("NTS_PROGRAM_COST", "")
    if raw == "0":
        return False
    if raw == "1":
        return True
    if metrics is not None and getattr(metrics, "path", None):
        return True
    return bool(os.environ.get("NTS_LEDGER_DIR"))


def memory_capture_enabled() -> bool:
    """``NTS_COST_MEMORY=1``: compile the capture lowering too, so
    jit-path programs (train steps) get the full memory analysis. Off by
    default — it doubles compile work where no persistent compile cache
    backs the run."""
    return os.environ.get("NTS_COST_MEMORY", "0") == "1"


def _first_module(analysis) -> Optional[Dict[str, Any]]:
    """cost_analysis() returns a dict on current jax, a one-per-module
    list on older releases; either way the program's numbers are the
    first module's."""
    if isinstance(analysis, dict):
        return analysis
    if isinstance(analysis, (list, tuple)) and analysis:
        first = analysis[0]
        return first if isinstance(first, dict) else None
    return None


def cost_from_analysis(analysis) -> Dict[str, Optional[float]]:
    """{flops, bytes_accessed, transcendentals} from one cost_analysis()
    result (nulls where the backend omits a key)."""
    d = _first_module(analysis) or {}

    def num(key):
        v = d.get(key)
        return float(v) if isinstance(v, (int, float)) else None

    return {
        "flops": num("flops"),
        "bytes_accessed": num("bytes accessed"),
        "transcendentals": num("transcendentals"),
    }


def memory_from_compiled(compiled) -> Optional[Dict[str, Optional[int]]]:
    """The memory_analysis() buffer-allocation numbers as a plain dict,
    or None when the backend exposes none. ``peak_bytes`` is the derived
    live-at-once envelope: arguments + outputs + temporaries (XLA's
    buffer assignment holds all three live across the program body)."""
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return None
    if ma is None:
        return None
    out: Dict[str, Optional[int]] = {}
    for attr, field in _MEM_FIELDS.items():
        v = getattr(ma, attr, None)
        out[field] = int(v) if isinstance(v, int) else None
    sized = [out.get(k) for k in ("argument_bytes", "output_bytes",
                                  "temp_bytes")]
    out["peak_bytes"] = (
        sum(v for v in sized if v is not None)
        if any(v is not None for v in sized) else None
    )
    if all(v is None for v in out.values()):
        return None
    return out


_CUSTOM_CALL_RE = re.compile(r"custom_call\s*@([\w.$-]+)")


def custom_call_targets(lowered_text: str) -> List[str]:
    """The custom-call targets a lowered (StableHLO) module holds, sorted
    and unique. A Pallas kernel lowered for the chip is a
    ``tpu_custom_call`` (Mosaic); the same kernel under ``interpret=True``
    leaves none — which is how a run shows its kernel was not emulated."""
    return sorted(set(_CUSTOM_CALL_RE.findall(lowered_text)))


def capture_program_cost(
    metrics,
    label: str,
    compiled=None,
    jitted=None,
    args: tuple = (),
    **extra: Any,
) -> Optional[Dict[str, Any]]:
    """Capture one program's cost as a typed ``program_cost`` record.

    ``metrics``: the run's MetricsRegistry (record lands in its stream
    AND in its run_summary ``program_costs`` list — bench.py's
    ``extra.metrics`` therefore carries it for free). Returns the record,
    or None when capture is disabled or the registry is absent. Never
    raises: a failed analysis emits ``available=false`` with the error.
    """
    if metrics is None or not cost_enabled(metrics):
        return None
    fields: Dict[str, Any] = {
        "label": str(label),
        "available": False,
        "source": "error",
        "flops": None,
        "bytes_accessed": None,
        "transcendentals": None,
        "memory": None,
    }
    try:
        import jax

        fields["platform"] = jax.default_backend()
    except Exception:
        fields["platform"] = None
    try:
        if compiled is None and jitted is not None:
            lowered = jitted.lower(*args)
            fields["custom_calls"] = custom_call_targets(lowered.as_text())
            if memory_capture_enabled():
                compiled = lowered.compile()
            else:
                fields.update(cost_from_analysis(lowered.cost_analysis()))
                fields["source"] = "lowered"
                fields["available"] = (
                    fields["flops"] is not None
                    or fields["bytes_accessed"] is not None
                )
        if compiled is not None:
            try:
                fields.update(cost_from_analysis(compiled.cost_analysis()))
            except Exception as e:
                fields["error"] = f"cost_analysis: {e}"
            fields["memory"] = memory_from_compiled(compiled)
            fields["source"] = "compiled"
            fields["available"] = (
                fields["flops"] is not None
                or fields["bytes_accessed"] is not None
                or fields["memory"] is not None
            )
        if not fields["available"] and "error" not in fields:
            # neither analysis yielded a number: a degraded backend —
            # the record still lands (queryable absence, not silence)
            fields.setdefault(
                "error", "backend exposed no cost or memory analysis"
            )
    except Exception as e:  # telemetry must never fail the program build
        fields["error"] = str(e)[:300]
        log.warning("program_cost capture failed for %s: %s", label, e)
    rec = metrics.event("program_cost", **dict(fields, **extra))
    record_list = getattr(metrics, "program_costs", None)
    if record_list is not None:
        record_list.append(
            {k: v for k, v in rec.items()
             if k not in ("event", "run_id", "schema", "seq")}
        )
    return rec
