"""Traffic of kind ``open_loop``: requests for per-vertex predictions sent
to the program's in-process server at a fixed rate.

Set-up builds the sampled trainer (whose weights, from the seed, are saved
as the checkpoint the engine restores), compiles the engine's ladder,
starts the server and sends ``warmup_requests`` through every bucket. The
window is ``--seconds`` of the seeded schedule; the cell returns when every
request due in it has an answer or has timed out.
"""

from __future__ import annotations

import gc
import time
from typing import Any, Dict, List, Tuple

import numpy as np

from harness import correct, openloop, program, runtime, spec

WARMUP_WAVE = 32
SERVE_COUNTERS = ("serve.computed_seeds", "serve.padded_seeds", "serve.batches", "serve.shed")


def build(ctx):
    """(inputs, trainer, engine, server, vertices) for the cell's
    configuration; shared with the knee sweep."""
    inputs_of = spec.config_module(ctx.config, "inputs")
    inputs, trainer = inputs_of.build(ctx)
    t = time.perf_counter()
    engine, server = program.build_server(trainer, ctx.work_dir, ctx.seed)
    ctx.spans["server_build_s"] = time.perf_counter() - t
    return inputs, trainer, engine, server, int(inputs_of.shape(inputs, trainer)["vertices"])


class GcLog:
    """The interpreter's garbage collections while it is open: (start on
    perf_counter's clock, seconds, generation). A collection stops every
    thread of the process, the server's and the generator's alike."""

    def __init__(self) -> None:
        self.pauses: List[Tuple[float, float, int]] = []
        self._start = 0.0

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._start = time.perf_counter()
        else:
            self.pauses.append((self._start, time.perf_counter() - self._start,
                                int(info["generation"])))

    def __enter__(self) -> "GcLog":
        gc.callbacks.append(self._on_gc)
        return self

    def __exit__(self, *exc) -> None:
        gc.callbacks.remove(self._on_gc)


def warm_up(server, mix: dict, vertices: int, seed: int) -> None:
    """Every request size, so every bucket and code path has run; in waves
    small enough for the server's queue."""
    rng = np.random.default_rng(seed + 1)
    sizes = list(mix["seeds_per_request"]["values"])
    for start in range(0, int(mix["warmup_requests"]), WARMUP_WAVE):
        wave = [
            server.submit(rng.integers(0, vertices, size=sizes[i % len(sizes)]))
            for i in range(start, start + WARMUP_WAVE)
        ]
        for req in wave:
            req.result(float(mix["timeout_s"]))


def offer(server, engine, mix: dict, vertices: int, seed: int, seconds: float,
          rate: float = None) -> Dict[str, Any]:
    """One window of the mix; the record the metric readers read."""
    schedule = openloop.make_schedule(seed, mix, vertices, seconds, rate)
    before = {c: program.counter(engine, c) for c in SERVE_COUNTERS}
    steal_before = runtime.cpu_steal_s()
    with GcLog() as gc_log:
        out = openloop.run_schedule(schedule, server.submit, float(mix["timeout_s"]))
    steal_s = None if steal_before is None else runtime.cpu_steal_s() - steal_before
    counters = {c: program.counter(engine, c) - before[c] for c in SERVE_COUNTERS}
    # the program's own marks on each request: time in its queue
    queue_ms = np.asarray([
        (r.t_flush - r.t_submit) * 1000.0 if r.t_flush is not None else np.nan
        for r in out.requests
    ])
    classes = engine.cfg.layer_sizes()[-1]
    well_formed = np.asarray([
        bool(ok) and r.logits is not None and r.logits.shape == (n, classes)
        and bool(np.all(np.isfinite(r.logits)))
        for r, ok, n in zip(out.requests, out.ok, out.n_seeds)
    ])
    return {
        "window": (out.t0, float(np.nanmax(out.done))),
        "offered_s": seconds,
        "latency_ms": out.latency_ms(float(mix["timeout_s"]) * 1000.0),
        "late_ms": out.late_ms,
        "queue_ms": queue_ms,
        "n_seeds": out.n_seeds,
        "gc_pauses": gc_log.pauses,
        "cpu_steal_s": steal_s,
        "counters": counters,
        "attempted": int(len(out.ok)),
        "failed": int(np.sum(~out.ok)),
        "malformed": int(np.sum(out.ok & ~well_formed)),
    }


def run_cell(ctx) -> Dict[str, Any]:
    mix, config = ctx.traffic, ctx.config
    inputs, trainer, engine, server, vertices = build(ctx)
    try:
        t = time.perf_counter()
        warm_up(server, mix, vertices, ctx.seed)
        ctx.spans["warmup_s"] = time.perf_counter() - t

        seconds = float(mix["trace_seconds"]) if ctx.trace else ctx.seconds
        if ctx.trace:
            ctx.profiler.start()
        record = offer(server, engine, mix, vertices, ctx.seed, seconds)
        if ctx.trace:
            ctx.stop_profiler()
        record["memory_peak_bytes"] = runtime.memory_peak_bytes(ctx.chips, ctx.rehearse)
        record["compile_counts"] = dict(engine.compile_counts)
    finally:
        server.close()

    t = time.perf_counter()
    record["engine"] = engine  # the check asks it for one answer of every size
    errors, faults = spec.config_module(config, "check").check(ctx, inputs, trainer, record)
    ctx.spans["check_s"] = time.perf_counter() - t
    # a served answer has no gradient: of the configuration's limits, which
    # it shares with the training cells, the answers are held to the logits'
    limits = correct.tolerance(config, ctx.rehearse)
    record["compared"] = correct.compare(
        errors, {"logits_rel": limits["logits_rel"]},
        faults=len(faults), malformed=record["malformed"],
    )
    record["correct"] = correct.passes(record["compared"])
    pauses = record["gc_pauses"]
    runtime.log(f"check in {ctx.spans['check_s']:.1f}s; faults {faults}; "
                f"{record['attempted']} requests, {record['failed']} failed, "
                f"counters {record['counters']}; {len(pauses)} garbage collections in the "
                f"window, by generation {[sum(g == k for _, _, g in pauses) for k in range(3)]}, "
                f"longest {max([s for _, s, _ in pauses], default=0.0) * 1000:.1f} ms; "
                f"CPU time stolen by the host {record['cpu_steal_s']} s")
    return record
