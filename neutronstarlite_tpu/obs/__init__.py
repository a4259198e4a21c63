"""Unified run-metrics subsystem (the observability spine).

The reference attributes every epoch to compute/copy/wait/comm buckets via
its ``DEBUGINFO()`` report (toolkits/GCN.hpp:308-353). This package gives the
TPU port one machine-readable telemetry surface over the signals that were
previously scattered across utils/timing (host phase timers),
models/debuginfo (bucket decomposition), tools/wire_accounting (exchange
volume) and ad-hoc bench prints:

- :class:`MetricsRegistry` — counters, gauges, timing summaries, plus a
  structured per-epoch JSONL event stream written under ``NTS_METRICS_DIR``;
- :mod:`collectors` — device memory, first and warm epoch times, the
  compiler's counters, phase-timer snapshots;
- :mod:`schema` — the JSONL event schema and its validator (tests and
  tools/metrics_report consume it);
- :mod:`trace` — hierarchical span tracing (trace_id / span_id /
  parent_id) over the same JSONL stream; tools/trace_timeline merges the
  per-rank span files into one causal timeline and a Chrome trace;
- :mod:`compiles` — every compile JAX reports as one ``compile`` span
  (function, trace / lower / backend seconds, cache hit) under the span
  that caused it, and the ``compile.*`` counters;
- :mod:`hist` — log-bucketed mergeable latency histograms (bounded
  relative quantile error, fixed memory) serialized as typed ``hist``
  records so tail quantiles survive rotation and multi-rank runs;
- :mod:`slo` — declarative objectives (``NTS_SLO_SPEC``) evaluated as
  rolling multi-window burn rates; the serve admission/shed signal;
- :mod:`exporter` — the opt-in HTTP pull endpoint (``NTS_METRICS_PORT``):
  /metrics (Prometheus text), /healthz, /slo;
- :mod:`flight` — the always-on bounded flight recorder: the last N
  records at full resolution, dumped on fault/breach/SIGUSR2;
- :mod:`cost` — compiled-program cost attribution: per-executable XLA
  ``cost_analysis()``/``memory_analysis()`` captured once at build time
  as typed ``program_cost`` records;
- :mod:`ledger` — the cross-run perf ledger (``NTS_LEDGER_DIR``): one
  atomically-appended row per run/suite/probe, keyed by graph digest +
  cfg fingerprint + backend; ``tools/perf_sentinel`` gates new rows
  against the MAD-scaled trend of their own history;
- :mod:`numerics` — the numerics health plane (``NTS_NUMERICS``):
  stats-fused step outputs as typed ``tensor_stats`` records, the
  one-shot non-finite provenance replay (``nonfinite_provenance``),
  the batched whole-tree finiteness check the guards use, and the
  measured wire quantization error (``NTS_QUANT_PROBE`` /
  ``NTS_QUANT_TOL``, audited by tools/drift_audit).

Every trainer run emits one ``run_summary`` record; ``tools/metrics_report``
renders one or more streams into the reference-shaped ``#key=value(ms)``
report and a cross-run comparison table. See docs/OBSERVABILITY.md.
"""

from neutronstarlite_tpu.obs.cost import capture_program_cost
from neutronstarlite_tpu.obs.hist import LogHistogram
from neutronstarlite_tpu.obs.registry import (
    MetricsRegistry,
    config_fingerprint,
    metrics_dir,
    open_run,
)
from neutronstarlite_tpu.obs.schema import SCHEMA_VERSION, validate_event
from neutronstarlite_tpu.obs.trace import Tracer

__all__ = [
    "LogHistogram",
    "MetricsRegistry",
    "SCHEMA_VERSION",
    "Tracer",
    "capture_program_cost",
    "config_fingerprint",
    "metrics_dir",
    "open_run",
    "validate_event",
]
