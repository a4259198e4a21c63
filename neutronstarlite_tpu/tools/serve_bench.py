"""Serving load generator: closed/open-loop SLO measurement.

``python -m neutronstarlite_tpu.tools.serve_bench <cfg> [<ckpt_dir>]
[--train] [--mode closed|open] [--clients C | --rps R] [--requests N]
[--replicas N] [--cb 0|1] [--delta-rate R]``

Drives the in-process serving stack (serve/server.py — or the
multi-replica fleet, serve/fleet.py, with ``--replicas N``) and reports
tail latency + throughput **from the obs records**: the serving run
writes its typed JSONL stream(s) (serve_request / batch_flush / shed /
serve_summary; one stream per replica in fleet mode, merged here through
the mergeable ``hist`` records) under NTS_METRICS_DIR (a temp dir when
unset), and the percentiles printed here are computed by re-reading
those streams — the measurement artifact is the same one
tools/metrics_report renders, not a private side channel.

Two load models:
- **closed** (default): C concurrent clients, each submits its next
  request only after the previous completes — measures capacity at a
  fixed concurrency (the classic closed-loop knee).
- **open**: requests arrive at a fixed rate R regardless of completions —
  measures behavior under offered load, including the shedding path once
  R exceeds capacity.

Fleet/live-graph legs:
- ``--replicas N`` serves through a ReplicaSet (SLO-routed, supervised);
- ``--cb 0|1`` pins continuous batching (SERVE_CB) for the run;
- ``--delta-rate R`` applies R live graph-delta batches per second
  (``--delta-edges`` random edge inserts each, the previous batch
  removed) DURING the load — the open-loop "predictions track a live
  graph" leg.
- ``--targets host:port,...`` drives already-running replica PROCESSES
  through the cross-host router (serve/crosshost) instead of building an
  in-process server; latency comes from the router's merged fleet
  histograms (``--v-num`` supplies the seed-id space).
- ``--trace`` (targets mode): after the load, merge the router's and
  the replicas' span streams (the tools/trace_timeline ``--fleet``
  cross-process join) and report the complete-chain fraction plus
  router-overhead p50/p95/p99 — client latency minus the replica's
  summed stage time, per traced request. The scalars ride the
  kind=serve ledger row so perf_sentinel can gate router_overhead_p99.
  ``--trace-dirs`` overrides which streams are merged (default:
  NTS_METRICS_DIR).

``--train`` first runs the cfg's training loop (with CHECKPOINT_DIR set
to the serving checkpoint dir) when no checkpoint exists yet — the
zero-to-serving path for smoke configs.

Prints ONE BENCH_*-compatible JSON line:
  {"metric": "serve_p99_latency_ms", "value": ..., "unit": "ms",
   "vs_baseline": null, "extra": {p50/p95/p99, throughput, sheds, ...}}

When ``NTS_LEDGER_DIR`` is set, one ``kind=serve`` row (p50/p95/p99,
shed rate, replica count, delta rate — keyed by cfg fingerprint + load
shape + graph digest) is appended to the cross-run perf ledger, so
``tools/perf_sentinel check --kind serve`` trend-gates serve latency the
way it already gates epoch time.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import threading
import time
from typing import Any, Dict

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from neutronstarlite_tpu.utils.logging import get_logger  # noqa: E402

log = get_logger("serve_bench")


def ensure_checkpoint(cfg, base_dir: str, ckpt_dir: str, train: bool) -> None:
    """Train the cfg's toolkit into ``ckpt_dir`` when empty and --train."""
    from neutronstarlite_tpu.utils.checkpoint import have_checkpoint

    if have_checkpoint(ckpt_dir, getattr(cfg, "ckpt_backend", "")):
        return
    if not train:
        raise SystemExit(
            f"no checkpoint under {ckpt_dir!r}; pass --train to train one "
            "from the cfg first"
        )
    from neutronstarlite_tpu.models import get_algorithm

    log.info("no checkpoint under %s; training %d epochs first",
             ckpt_dir, cfg.epochs)
    prev = os.environ.get("NTS_SAMPLE_WORKERS")
    os.environ.setdefault("NTS_SAMPLE_WORKERS", "0")
    try:
        toolkit = get_algorithm(cfg.algorithm)(cfg, base_dir=base_dir)
        toolkit.init_graph()
        toolkit.init_nn()
        toolkit.run()
    finally:
        if prev is None:
            os.environ.pop("NTS_SAMPLE_WORKERS", None)


def run_closed_loop(server, v_num: int, n_requests: int, clients: int,
                    seeds_per_request: int, seed: int) -> int:
    """C clients, each with one request outstanding; returns error count."""
    counter = {"next": 0, "errors": 0}
    lock = threading.Lock()

    def client(idx: int) -> None:
        rng = np.random.default_rng(seed + 1000 + idx)
        while True:
            with lock:
                if counter["next"] >= n_requests:
                    return
                counter["next"] += 1
            req = server.submit(rng.integers(0, v_num, seeds_per_request))
            try:
                req.result(timeout=120.0)
            except Exception:
                with lock:
                    counter["errors"] += 1

    threads = [
        threading.Thread(target=client, args=(i,), daemon=True)
        for i in range(max(clients, 1))
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return counter["errors"]


def run_open_loop(server, v_num: int, n_requests: int, rps: float,
                  seeds_per_request: int, seed: int) -> int:
    """Fixed arrival rate; sheds count as completed-with-error."""
    rng = np.random.default_rng(seed + 2000)
    interval = 1.0 / max(rps, 1e-6)
    pending = []
    t_next = time.perf_counter()
    for _ in range(n_requests):
        now = time.perf_counter()
        if now < t_next:
            time.sleep(t_next - now)
        t_next += interval
        pending.append(
            server.submit(rng.integers(0, v_num, seeds_per_request))
        )
    errors = 0
    for req in pending:
        try:
            req.result(timeout=120.0)
        except Exception:
            errors += 1
    return errors


def percentiles_from_streams(paths) -> Dict[str, Any]:
    """Recompute the SLO numbers from one or many serving obs streams
    (fleet mode: one stream per replica + the front door).

    Quantiles come from the streams' merged ``hist`` records (obs/hist:
    cumulative snapshots, fixed memory, survive NTS_METRICS_MAX_MB
    rotation, and MERGE across replicas — the fleet p99 is exact); the
    raw full-sort of every serve_request line — O(N) memory and blind to
    rotated-away requests — is only the fallback for pre-histogram
    streams. A rotated ``<path>.1`` chunk is read first so counts cover
    the whole run where it survived."""
    from neutronstarlite_tpu.obs import schema
    from neutronstarlite_tpu.obs.hist import latest_hists

    events = []
    for path in paths:
        rotated = path + ".1"
        chunks = [rotated, path] if os.path.exists(rotated) else [path]
        for chunk in chunks:
            with open(chunk, "r", encoding="utf-8") as fh:
                for raw in fh:
                    raw = raw.strip()
                    if not raw:
                        continue
                    obj = json.loads(raw)
                    schema.validate_event(obj)
                    events.append(obj)
    reqs = [e for e in events if e["event"] == "serve_request"]
    served = [
        e for e in reqs
        if e["status"] != "shed" and e.get("total_ms") is not None
    ]
    ts = [e["ts"] for e in served]
    summary = None
    for e in events:
        if e["event"] == "serve_summary":
            summary = e
    out: Dict[str, Any] = {
        "served": len(served),
        "shed": sum(1 for e in reqs if e["status"] == "shed"),
        "batches": sum(1 for e in events if e["event"] == "batch_flush"),
        "summary": summary,
    }
    h = latest_hists(events).get("serve.latency_ms")
    if h is not None and h.count:
        out["latency_ms"] = h.quantiles()
        out["latency_source"] = "hist"
        out["served"] = max(out["served"], h.count)
    elif served:
        lat = [e["total_ms"] for e in served]
        p50, p95, p99 = np.percentile(np.asarray(lat), [50, 95, 99])
        out["latency_ms"] = {
            "p50": float(p50), "p95": float(p95), "p99": float(p99),
        }
        out["latency_source"] = "raw"
    else:
        out["latency_ms"] = {"p50": None, "p95": None, "p99": None}
        out["latency_source"] = None
    span = (max(ts) - min(ts)) if len(ts) > 1 else 0.0
    out["throughput_rps"] = len(ts) / span if span > 0 else None
    return out


def percentiles_from_stream(path: str) -> Dict[str, Any]:
    """Single-stream wrapper (the pre-fleet entry point)."""
    return percentiles_from_streams([path])


def run_delta_loop(target, rate: float, edges_per_delta: int, seed: int,
                   stop: threading.Event, counts: Dict[str, int]) -> None:
    """Apply live graph-delta batches at ``rate``/s while the load runs:
    each batch inserts ``edges_per_delta`` random NOVEL edges and removes
    the previous batch's — the graph keeps changing, its size stays
    bounded, and the base graph is never damaged. Novelty matters:
    removal drops EVERY occurrence of a listed pair, so a random insert
    that collided with a pre-existing edge would take the original down
    with it on the next round — candidates are filtered against the
    current edge set (one O(E) key build per batch; bench scale).
    ``target`` is an InferenceServer or ReplicaSet (both expose
    apply_delta)."""
    from neutronstarlite_tpu.serve.delta import GraphDelta, _edge_keys

    rng = np.random.default_rng(seed + 31337)
    interval = 1.0 / max(rate, 1e-6)
    last: list = []
    while not stop.wait(interval):
        g = target.engine.sampler.graph
        v = g.v_num
        existing = set(_edge_keys(
            g.row_indices.astype(np.int64), g.dst_of_edge.astype(np.int64)
        ).tolist())
        add: list = []
        chosen = set()
        for _ in range(20 * max(edges_per_delta, 1)):  # bounded tries
            if len(add) >= max(edges_per_delta, 1):
                break
            u, w = int(rng.integers(0, v)), int(rng.integers(0, v))
            key = (u << 32) | w
            if key in existing or key in chosen:
                continue
            chosen.add(key)
            add.append((u, w))
        if not add:
            continue
        try:
            target.apply_delta(GraphDelta.edges(add=add, remove=last))
        except Exception as e:  # the load must finish; deltas are the leg
            log.warning("delta application failed (%s); stopping deltas", e)
            return
        last = add
        counts["applied"] += 1


def _run_targets_mode(args) -> int:
    """Drive already-running replica processes through the cross-host
    router (serve/crosshost): same load loops, same front-door contract
    (``submit()`` -> future), latency from the router's merged fleet
    histograms (the exact bucket-addition view) instead of local
    streams."""
    from neutronstarlite_tpu.serve.crosshost import CrossHostFleet

    targets = [t.strip() for t in args.targets.split(",") if t.strip()]
    if args.trace and not os.environ.get("NTS_METRICS_DIR"):
        # the router's span stream must land somewhere readable: the
        # post-run merge joins it with the replicas' streams by trace_id
        os.environ["NTS_METRICS_DIR"] = tempfile.mkdtemp(
            prefix="nts_serve_bench_trace_"
        )
    fleet = CrossHostFleet.from_targets(targets)
    t0 = time.perf_counter()
    try:
        if args.mode == "closed":
            errors = run_closed_loop(
                fleet, args.v_num, args.requests, args.clients,
                args.seeds_per_request, args.seed,
            )
        else:
            errors = run_open_loop(
                fleet, args.v_num, args.requests, args.rps,
                args.seeds_per_request, args.seed,
            )
        wall_s = time.perf_counter() - t0
    finally:
        stats = fleet.close()
    trace_view: Dict[str, Any] = {}
    if args.trace:
        # merge the router's + replicas' span streams (all processes
        # share NTS_METRICS_DIR, or pass --trace-dirs) and derive the
        # per-request chain verdict the same way trace_timeline --fleet
        # does — the measurement artifact is the shared obs streams
        from neutronstarlite_tpu.tools.metrics_report import (
            expand_paths,
            load_events,
        )
        from neutronstarlite_tpu.tools.trace_timeline import (
            request_tracing_report,
        )

        dirs = (args.trace_dirs or
                [os.environ.get("NTS_METRICS_DIR", "")])
        merged = []
        for p in expand_paths([d for d in dirs if d]):
            try:
                merged.extend(load_events(p))
            except OSError as e:
                log.warning("serve_bench --trace: cannot read %s (%s)",
                            p, e)
        rep = request_tracing_report(merged)
        if rep is None:
            log.warning("serve_bench --trace: no request traces found "
                        "(is NTS_TRACE on in the replicas?)")
        else:
            trace_view = {
                "trace_complete_frac": rep["complete_frac"],
                "trace_chains": rep["n_traces"],
                "router_overhead_p50_ms": rep["router_overhead_p50_ms"],
                "router_overhead_p95_ms": rep["router_overhead_p95_ms"],
                "router_overhead_p99_ms": rep["router_overhead_p99_ms"],
            }
    lat = stats["latency_ms"]
    result = {
        "metric": "serve_p99_latency_ms",
        "value": lat.get("p99"),
        "unit": "ms",
        "vs_baseline": None,
        "extra": {
            "mode": args.mode,
            "clients": args.clients if args.mode == "closed" else None,
            "rps_offered": args.rps if args.mode == "open" else None,
            "requests": args.requests,
            "seeds_per_request": args.seeds_per_request,
            "p50_ms": lat.get("p50"),
            "p95_ms": lat.get("p95"),
            "p99_ms": lat.get("p99"),
            "latency_source": "fleet_hist",
            "served": stats["requests"],
            "shed": stats["shed"],
            "errors": errors,
            "restarts": stats["restarts"],
            "targets": targets,
            "replicas": stats["replicas"],
            "targets_lost": stats["targets_lost"],
            "wall_s": wall_s,
            **trace_view,
        },
    }
    # one kind=serve row (NTS_LEDGER_DIR): targets-mode runs share the
    # serve trajectory keyed by the target count; with --trace the row
    # carries router_overhead_* + trace_complete_frac, which
    # perf_sentinel gates like any serve scalar
    from neutronstarlite_tpu.obs import ledger

    if ledger.ledger_dir():
        served = stats["requests"]
        shed = stats["shed"]
        total = served + shed
        ledger.append_row(ledger.serve_row(
            latency_ms=lat,
            shed_rate=(shed / total) if total > 0 else None,
            throughput_rps=stats.get("throughput_rps"),
            requests=args.requests,
            cfg_fingerprint=f"targets{len(targets)}",
            graph_digest=None,
            mode=args.mode,
            replicas=stats["replicas"],
            continuous_batching=False,
            extra={
                "clients": (
                    args.clients if args.mode == "closed" else None
                ),
                "rps_offered": (
                    args.rps if args.mode == "open" else None
                ),
                **trace_view,
            },
        ))
    print(json.dumps(result))
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="closed/open-loop serving benchmark over the serve/ "
        "stack; prints one BENCH-compatible JSON line"
    )
    ap.add_argument("cfg", nargs="?", default="",
                    help="cfg file (unused in --targets mode)")
    ap.add_argument("ckpt", nargs="?", default="",
                    help="checkpoint dir (default: cfg CHECKPOINT_DIR, "
                    "or a temp dir with --train)")
    ap.add_argument("--train", action="store_true",
                    help="train the cfg first when no checkpoint exists")
    ap.add_argument("--mode", choices=("closed", "open"), default="closed")
    ap.add_argument("--clients", type=int, default=4,
                    help="closed-loop concurrency")
    ap.add_argument("--rps", type=float, default=200.0,
                    help="open-loop arrival rate")
    ap.add_argument("--requests", type=int, default=200)
    ap.add_argument("--seeds-per-request", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--replicas", type=int, default=None,
                    help="serve through an N-replica ReplicaSet "
                    "(default: cfg SERVE_REPLICAS / NTS_SERVE_REPLICAS)")
    ap.add_argument("--route", choices=("least_burn", "round_robin"),
                    default=None, help="fleet routing policy override")
    ap.add_argument("--cb", choices=("0", "1"), default=None,
                    help="pin continuous batching (SERVE_CB) for the run")
    ap.add_argument("--delta-rate", type=float, default=0.0,
                    help="apply this many live graph-delta batches per "
                    "second during the load (0 = frozen graph)")
    ap.add_argument("--delta-edges", type=int, default=4,
                    help="edge inserts per delta batch (the previous "
                    "batch is removed)")
    ap.add_argument("--targets", default=None,
                    help="drive a cross-host fleet (serve/crosshost) at "
                    "these replica addresses instead of an in-process "
                    "server; cfg/ckpt are ignored")
    ap.add_argument("--v-num", type=int, default=2708,
                    help="seed-id space for --targets mode (the remote "
                    "graph is not introspectable)")
    ap.add_argument("--trace", action="store_true",
                    help="targets mode: distributed request tracing — "
                    "after the load, merge the router's + replicas' span "
                    "streams (trace_timeline --fleet join) and report "
                    "complete-chain fraction + router-overhead "
                    "p50/p95/p99 (requires NTS_TRACE on in the replicas)")
    ap.add_argument("--trace-dirs", nargs="+", default=None,
                    help="metrics dirs/files holding the fleet's span "
                    "streams (default: NTS_METRICS_DIR)")
    args = ap.parse_args(argv)
    if args.targets:
        # a pure HTTP client: it must not open the device the replicas need
        return _run_targets_mode(args)
    if not args.cfg:
        ap.error("cfg is required without --targets")
    from neutronstarlite_tpu.utils.platform import (
        configure_compile_cache,
        start_runtime,
    )

    configure_compile_cache()
    if args.cb is not None:
        os.environ["NTS_SERVE_CB"] = args.cb
    if args.route is not None:
        os.environ["NTS_SERVE_ROUTE"] = args.route

    from neutronstarlite_tpu.utils.config import InputInfo

    cfg = InputInfo.read_from_cfg_file(args.cfg)
    base_dir = os.path.dirname(os.path.abspath(args.cfg))
    scratch = None
    ckpt_dir = args.ckpt or cfg.checkpoint_dir
    if not ckpt_dir:
        if not args.train:
            raise SystemExit(
                "no checkpoint dir: pass one, set CHECKPOINT_DIR in the "
                "cfg, or use --train"
            )
        scratch = tempfile.mkdtemp(prefix="nts_serve_bench_")
        ckpt_dir = os.path.join(scratch, "ckpt")
    cfg.checkpoint_dir = ckpt_dir
    if not os.environ.get("NTS_METRICS_DIR"):
        # the SLO numbers below are read back from this stream
        os.environ["NTS_METRICS_DIR"] = (
            scratch or tempfile.mkdtemp(prefix="nts_serve_bench_")
        )

    ensure_checkpoint(cfg, base_dir, ckpt_dir, args.train)

    from neutronstarlite_tpu.serve.engine import (
        InferenceEngine,
        ServeSetupError,
    )
    from neutronstarlite_tpu.serve.server import InferenceServer

    try:
        engine = InferenceEngine.from_config(
            cfg, base_dir=base_dir, ckpt_dir=ckpt_dir,
            rng=np.random.default_rng(args.seed),
        )
    except ServeSetupError as e:
        raise SystemExit(f"serve_bench: {e}")
    start_runtime()  # after the toolkits forked their sampler pools
    from neutronstarlite_tpu.serve.fleet import FleetOptions, ReplicaSet

    t0 = time.perf_counter()
    engine.warmup()
    warmup_s = time.perf_counter() - t0
    replicas = (
        args.replicas if args.replicas is not None
        else FleetOptions.from_cfg(cfg).replicas
    )
    if replicas > 1:
        server = ReplicaSet.from_engine(
            engine, replicas, seed=args.seed
        )
        stream_paths = server.stream_paths()
    else:
        server = InferenceServer(engine)
        stream_paths = [engine.metrics.path] if engine.metrics.path else []
    v_num = engine.toolkit.host_graph.v_num
    # the PRE-delta digest is the run's workload identity: the ledger row
    # must key on it, or two --delta-rate runs (whose applied-delta count
    # depends on wall-clock timing) would never share a trajectory and
    # the serve sentinel would silently never gate them
    initial_digest = engine.graph_digest()

    delta_stop = threading.Event()
    delta_counts = {"applied": 0}
    delta_thread = None
    if args.delta_rate > 0:
        delta_thread = threading.Thread(
            target=run_delta_loop,
            args=(server, args.delta_rate, args.delta_edges, args.seed,
                  delta_stop, delta_counts),
            daemon=True,
        )
        delta_thread.start()

    t0 = time.perf_counter()
    if args.mode == "closed":
        errors = run_closed_loop(
            server, v_num, args.requests, args.clients,
            args.seeds_per_request, args.seed,
        )
    else:
        errors = run_open_loop(
            server, v_num, args.requests, args.rps,
            args.seeds_per_request, args.seed,
        )
    wall_s = time.perf_counter() - t0
    delta_stop.set()
    if delta_thread is not None:
        delta_thread.join(timeout=30.0)
    # the graph digest the run ENDED on (deltas bump it) — the ledger key
    graph_digest = engine.graph_digest()
    stats = server.close()
    if replicas > 1:
        # normalize the fleet stats onto the single-server report shape:
        # the AOT ladder is SHARED across replicas (clone warm start), so
        # r0's compile counts are the fleet's; cache stats sum
        per = stats.get("per_replica") or {}
        first = per.get("r0") or {}
        stats["compile_counts"] = first.get("compile_counts", {})
        agg: Dict[str, int] = {}
        for s in per.values():
            for k, v in (s.get("cache") or {}).items():
                agg[k] = agg.get(k, 0) + int(v)
        stats["cache"] = agg

    stream_paths = [p for p in stream_paths if p and os.path.exists(p)]
    if stream_paths:
        obs_view = percentiles_from_streams(stream_paths)
    else:  # metrics dir unusable: fall back to the in-memory view
        obs_view = {
            "served": stats["requests"], "shed": stats["shed"],
            "batches": None, "latency_ms": stats["latency_ms"],
            "throughput_rps": stats["throughput_rps"], "summary": None,
        }
    stream_path = stream_paths[0] if stream_paths else None
    lat = obs_view["latency_ms"]
    # the serving-side sampling-pipeline telemetry (SAMPLE_PIPELINE:
    # pipelined/device): queue depth + residual stall ride the
    # serve_summary record's registry snapshot, so the open-loop p99
    # report carries the overlap verdict next to the latency it buys
    summary = obs_view.get("summary") or {}
    s_counters = summary.get("counters") or {}
    s_gauges = summary.get("gauges") or {}
    result = {
        "metric": "serve_p99_latency_ms",
        "value": lat["p99"],
        "unit": "ms",
        "vs_baseline": None,
        "extra": {
            "mode": args.mode,
            "clients": args.clients if args.mode == "closed" else None,
            "rps_offered": args.rps if args.mode == "open" else None,
            "requests": args.requests,
            "seeds_per_request": args.seeds_per_request,
            "p50_ms": lat["p50"],
            "p95_ms": lat["p95"],
            "p99_ms": lat["p99"],
            "throughput_rps": obs_view["throughput_rps"],
            "latency_source": obs_view.get("latency_source"),
            "served": obs_view["served"],
            "shed": obs_view["shed"],
            "errors": errors,
            "batches": obs_view["batches"],
            "warmup_compile_s": warmup_s,
            "compile_counts": {
                str(k): v for k, v in stats["compile_counts"].items()
            },
            "cache": stats["cache"],
            "sample_pipeline": engine.opts.sample_pipeline,
            "sample_queue_depth": s_gauges.get("sample.queue_depth"),
            "sample_stall_ms": s_counters.get("sample.stall_ms"),
            "continuous_batching": engine.opts.continuous_batching,
            "replicas": replicas,
            "fleet_shed": stats.get("fleet_shed"),
            "restarts": stats.get("restarts"),
            "delta_rate": args.delta_rate,
            "deltas_applied": delta_counts["applied"],
            "graph_digest": graph_digest,
            "wall_s": wall_s,
            "metrics_stream": stream_path,
        },
    }
    # one kind=serve row into the cross-run perf ledger (NTS_LEDGER_DIR):
    # perf_sentinel check --kind serve trend-gates these the way it
    # gates epoch time (key embeds mode/replicas/CB — no mixed shapes)
    from neutronstarlite_tpu.obs import config_fingerprint, ledger

    if ledger.ledger_dir():
        served = obs_view["served"]
        shed = obs_view["shed"]
        total = served + shed
        ledger.append_row(ledger.serve_row(
            latency_ms=lat,
            shed_rate=(shed / total) if total > 0 else None,
            throughput_rps=obs_view["throughput_rps"],
            requests=args.requests,
            cfg_fingerprint=config_fingerprint(cfg),
            graph_digest=initial_digest,
            mode=args.mode,
            replicas=replicas,
            continuous_batching=engine.opts.continuous_batching,
            delta_rate=args.delta_rate,
            deltas_applied=delta_counts["applied"],
            extra={
                "clients": args.clients if args.mode == "closed" else None,
                "rps_offered": args.rps if args.mode == "open" else None,
            },
        ))
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
