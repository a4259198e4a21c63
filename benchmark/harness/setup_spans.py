"""Set-up by the program's own spans: the runtime's start, every compile,
and what no span covers.

Pure functions over the records ``program_spans.span_records()`` hands out
and the stamps of a run (the benchmark's process stamp, the window's
start), all on ``time.perf_counter``. The program's ``startup`` spans
(``process_prelude``: from the kernel's start of the process to the
program's import; ``backend_init``) and its ``compile`` spans (one per
request of JAX's compiler: ``fun``, ``trace_s``, ``lower_s``,
``backend_s``, ``retrieve_s``, ``cache``) are what a program of this PR on
has and an older one has not: with neither in the records every function
here gives None. A compile belongs to set-up when it ended before the
window's start; what the check compiles after the window is under no span
of the program and is not in the records at all.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

from .trace_reduce import clip, subtract, total, union

STARTUP, COMPILE = "startup", "compile"
# the run root holds the constructor's gaps as well as its phases: left out
SETUP_CATS = (STARTUP, "phase", "epoch", "stage", COMPILE)
NO_SPAN = "no span after "


def _end(record: Dict) -> float:
    return record["t0"] + record["dur_s"]


def has_setup_spans(records: Optional[List[Dict]]) -> bool:
    return any(r["cat"] in (STARTUP, COMPILE) for r in records or [])


def runtime_start_s(records: Optional[List[Dict]]) -> Optional[float]:
    """The ``startup`` spans: ``process_prelude`` plus every ``backend_init``."""
    found = [r["dur_s"] for r in records or [] if r["cat"] == STARTUP]
    return float(sum(found)) if found else None


def setup_compiles(records: Optional[List[Dict]], window_start: float) -> Optional[List[Dict]]:
    """The ``compile`` spans that ended before the window's start; None
    for a program that records none at all."""
    compiles = [r for r in records or [] if r["cat"] == COMPILE]
    return [r for r in compiles if _end(r) <= window_start] if compiles else None


def parts(compiles: Optional[List[Dict]]) -> Optional[Dict[str, float]]:
    """Seconds tracing, lowering and in the backend (the compile, or the
    cache read and the load), summed over the spans."""
    if compiles is None:
        return None
    return {key: float(sum(r[key] for r in compiles)) for key in ("trace_s", "lower_s", "backend_s")}


def cache_misses(compiles: Optional[List[Dict]]) -> Optional[float]:
    return None if compiles is None else float(sum(1 for r in compiles if r["cache"] == "miss"))


def first_epoch_compiles(records: Optional[List[Dict]]) -> Optional[List[Dict]]:
    """The ``compile`` spans under the run's first ``epoch`` span, at any
    depth (the step's is under its ``step_dispatch``)."""
    records = records or []
    epoch = next((r for r in records if r["name"] == "epoch"), None)
    if epoch is None or not any(r["cat"] == COMPILE for r in records):
        return None
    parent = {r["span_id"]: r["parent_id"] for r in records}

    def under_epoch(span_id) -> bool:
        while span_id is not None and span_id != epoch["span_id"]:
            span_id = parent.get(span_id)
        return span_id is not None

    return [r for r in records if r["cat"] == COMPILE and under_epoch(r["parent_id"])]


def setup_by_span(records: Optional[List[Dict]], t_process: float,
                  window_start: float) -> Optional[List[Tuple[str, float]]]:
    """[(label, seconds)] of the time from the benchmark's process stamp to
    the window's start, longest first: each span of ``SETUP_CATS`` by its
    own time (its interval less the spans it is the parent of) under its
    name (a compile's with its ``fun``) and, for every stretch that none
    of them covers, ``no span after <the span that ended last before it>``.
    None for a program without the spans of this module."""
    if not has_setup_spans(records):
        return None
    spans = [r for r in records if r["cat"] in SETUP_CATS]
    children: Dict[str, List] = {}
    for r in spans:
        children.setdefault(r["parent_id"], []).append((r["t0"], _end(r)))
    acc: Dict[str, float] = {}
    for r in spans:
        mine = subtract([(r["t0"], _end(r))], union(children.get(r["span_id"], [])))
        seconds = total(clip(mine, t_process, window_start))
        if seconds > 0.0:
            label = f"compile {r['fun']}" if r["cat"] == COMPILE else r["name"]
            acc[label] = acc.get(label, 0.0) + seconds
    covered = union(clip([(r["t0"], _end(r)) for r in spans], t_process, window_start))
    ends = sorted((_end(r), r["name"]) for r in spans)
    for lo, hi in subtract([(t_process, window_start)], covered):
        before = [name for end, name in ends if end <= lo + 1e-9]
        label = NO_SPAN + (before[-1] if before else "the process's start")
        acc[label] = acc.get(label, 0.0) + hi - lo
    return sorted(acc.items(), key=lambda kv: -kv[1])


def unspanned_s(split: Optional[List[Tuple[str, float]]], own_s: float = 0.0) -> Optional[float]:
    """Seconds of a ``setup_by_span`` split under no span, less ``own_s``:
    the benchmark's own spans (the graph, the datum), which run while the
    program has no tracer and so lie in those stretches."""
    if split is None:
        return None
    return float(sum(s for label, s in split if label.startswith(NO_SPAN))) - own_s


def gauge(name: str) -> Optional[float]:
    """A gauge of the program's newest registry; None where the program
    has no such accessor, no registry yet or no such gauge."""
    try:
        from neutronstarlite_tpu.obs.trace import newest
    except ImportError:
        return None
    tracer = newest()
    if tracer is None:
        return None
    value = tracer.registry.snapshot(include_hists=False)["gauges"].get(name)
    return None if value is None else float(value)
