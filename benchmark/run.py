"""One cell, once, in one process.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Reads the cell from BENCHMARK.json, its configuration from
benchmark/configs/, its traffic from benchmark/traffic/, the kind of that
traffic from benchmark/kinds/, the modules the configuration names (its
inputs, its check, its count) and, with ``--trace 1``, one reader per
per-layer metric from benchmark/layer_metrics/, all found by name
(harness/spec.py lists them). Fails, with no result line, when JAX reports
anything but a TPU with the chips the cell asks for. The last line of
stdout is the result: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, when traced ``breakdown``, and last ``compared``: every number
the check compared, beside its limit. The same numbers are the last lines
of stderr.

``--rehearse`` runs the same path end to end on the CPU at the
configuration's tiny rehearsal size (a four-chip cell on four virtual
devices) and prints which metrics would be reported, never a value: a
number from a CPU run is not a device metric.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import multiprocessing  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

from harness import correct, e2e, runtime, spec  # noqa: E402


class Context:
    """What one run carries from set-up to the metric readers."""

    def __init__(self, bench: dict, cell: dict, device: dict, seed: int,
                 seconds: float, trace: bool, rehearse: bool) -> None:
        self.bench = bench
        self.cell = cell
        self.workload = cell["name"]
        self.config = cell["config_data"]
        self.traffic = cell["traffic_data"]
        self.chips = int(cell["chips"])
        self.seed = seed
        self.seconds = float(seconds)
        self.trace = trace
        self.rehearse = rehearse
        self.device = device
        self.t_process_start = T_PROCESS_START
        self.spans: dict = {}
        self.cache_root = spec.CACHE_DIR
        self.work_dir = os.path.join(spec.CACHE_DIR, "runs", self.workload)
        shutil.rmtree(self.work_dir, ignore_errors=True)
        os.makedirs(self.work_dir)
        self.compile_log = runtime.CompileLog()
        self.profiler = runtime.Profiler(
            os.path.join(self.work_dir, "trace"), self.traffic.get("python_tracer", True)
        )
        self.trace_file = None
        self._reduction = None

    def stop_profiler(self) -> None:
        self.trace_file = self.profiler.stop()

    @property
    def reduction(self):
        """The trace reduced (once); None in a run that was not traced."""
        if self._reduction is None and self.trace_file and not self.rehearse:
            from harness import trace_reduce

            self._reduction = trace_reduce.reduce_file(
                self.trace_file, runtime.Profiler.WINDOW, self.chips
            )
        return self._reduction  # a CPU rehearsal's trace has no device plane

    @property
    def peaks(self) -> dict:
        return spec.load_peaks(self.device["kind"])


def collect_metrics(ctx: Context, record: dict) -> dict:
    out = {}
    if ctx.trace:
        for m in spec.metrics_for(ctx.bench, "per_layer", ctx.workload):
            value = spec.layer_reader(m["name"])(ctx, record)
            if value is not None and math.isfinite(value):
                out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    else:
        for m in spec.metrics_for(ctx.bench, "end_to_end", ctx.workload):
            value = e2e.READERS[m["name"]](ctx, record)
            if value is not None:
                out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def log_compared(compared: dict) -> None:
    """Every number compared beside its limit: the last lines of stderr."""
    for name, c in compared.items():
        runtime.log(f"compared {name}: {c['value']} against the limit {c['limit']}")


def open_context(workload: str, seed: int, seconds: float, trace: bool,
                 rehearse: bool, chips: int = None):
    """The cell's files, its environment, the device gate and the compile
    cache, in the order they must come; None (after saying why) where JAX
    does not report the device the cell asks for. ``chips``: what a tool
    that runs no trainer (the control: the reference alone, on one device)
    needs in place of the cell's own number."""
    bench = spec.load_benchmark()
    cell = spec.load_cell(bench, workload)
    cell["chips"] = chips = int(cell["chips"] if chips is None else chips)
    os.environ.update({k: str(v) for k, v in cell["config_data"].get("env", {}).items()})
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={chips}"
        )
    sys.path.insert(0, spec.REPO)  # the system under test

    try:
        device = runtime.require_device(chips, rehearse)
    except runtime.DeviceError as e:
        print(f"benchmark: {e}; nothing was run", file=sys.stderr, flush=True)
        return None
    from harness import program

    cache_dir = program.configure_compile_cache()
    runtime.log(f"cell {workload} seed {seed} on {device}; compile cache {cache_dir}")
    return Context(bench, cell, device, seed, seconds, trace, rehearse)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU run at the rehearsal size; prints no device metric")
    args = ap.parse_args(argv)

    ctx = open_context(args.workload, args.seed, args.seconds, bool(args.trace), args.rehearse)
    if ctx is None:
        return 3
    try:
        record = spec.traffic_kind(ctx.traffic["kind"])(ctx)
        t0, t1 = record["window"]
        record["compiles_in_window"] = ctx.compile_log.requests_between(t0, t1)
        metrics = collect_metrics(ctx, record)
    finally:
        for child in multiprocessing.active_children():
            child.terminate()
            child.join()

    runtime.log(f"spans {json.dumps({k: round(v, 3) for k, v in ctx.spans.items()})}; "
                f"compile {ctx.compile_log.compile_s:.1f}s in "
                f"{len(ctx.compile_log.requests)} requests, {ctx.compile_log.hits} cache hits; "
                f"{record['compiles_in_window']} requests in the window")
    compared = correct.printable(record["compared"])
    if args.rehearse:
        log_compared(compared)
        print(json.dumps({
            "rehearsal": True, "correct": record["correct"],
            "attempted": record["attempted"], "failed": record["failed"],
            "would_report": sorted(metrics), "device": ctx.device,
            "compiles_in_window": record["compiles_in_window"], "compared": compared,
        }), flush=True)
        return 0 if record["correct"] else 1

    result = {
        "correct": bool(record["correct"]),
        "attempted": int(record["attempted"]),
        "failed": int(record["failed"]),
        "metrics": metrics,
        "device": dict(ctx.device, memory_peak_bytes=record["memory_peak_bytes"]),
    }
    if ctx.trace:
        red = ctx.reduction
        result["device"].update(busy_s=red.busy_s, window_s=red.window_s)
        result["breakdown"] = {"device_ops": red.top_ops(10), "idle_gaps": red.idle_gaps(10)}
    result["compared"] = compared
    log_compared(compared)
    print(json.dumps(result), flush=True)  # nothing may follow it on stdout
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except Exception:  # no result line: a failed run prints none
        traceback.print_exc()
        sys.exit(1)
