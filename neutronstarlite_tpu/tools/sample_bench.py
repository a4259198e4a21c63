"""Sampling-pipeline benchmark: steady-state batches/sec, sync vs pipelined.

tools/bench_sample.py measures the sampled path's per-batch cost with the
sample+step chain run SERIALLY — the upper bound the async pipeline
(sample/pipeline.py) is built to beat. This leg runs the actual trainer
epoch loop in two (or three) SAMPLE_PIPELINE modes over ONE shared host
graph (one native build — tie-edge order is nondeterministic across
builds, and a shared graph keeps sync/pipelined bitwise-comparable) and
reports steady-state batches/sec per mode plus the telemetry that explains
the difference: the synchronous path's serial sample time vs the pipelined
path's residual ``sample.stall_ms`` vs the fused path's dispatch count
(one ``lax.scan`` per epoch, ``sample.h2d_bytes`` exactly 0 —
sample/fused.py).

With ``NTS_LEDGER_DIR`` set, each measured mode also lands one kind=run
row in the cross-run perf ledger (cfg key ``sample_bench/<mode>`` so the
modes never share a trajectory): perf_sentinel trend-gates the
steady-state ``warm_median_epoch_s`` per mode, and the batches/s +
dispatch counts ride along as context.

Usage: python -m neutronstarlite_tpu.tools.sample_bench [--scale S]
         [--batch-size 512] [--fanout 25-10] [--epochs 3]
         [--modes sync,pipelined,fused]
Prints ONE BENCH-style JSON line:
  {"metric": "sample_pipeline_batches_per_sec", "value": <pipelined bps>,
   "extra": {per-mode epoch times, stall/sample ms, loss parity}}
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def measure_mode(mode, cfg_proto, src, dst, datum, host_graph):
    import jax

    from neutronstarlite_tpu.models.gcn_sample import GCNSampleTrainer

    import dataclasses

    cfg = dataclasses.replace(cfg_proto, sample_pipeline=(
        "" if mode == "sync" else mode
    ))
    t0 = time.time()
    tr = GCNSampleTrainer.from_arrays(
        cfg, src, dst, datum, host_graph=host_graph
    )
    result = tr.run()
    wall_s = time.time() - t0
    snap = tr.metrics.snapshot()
    counters = snap["counters"]
    epochs = tr.epoch_times
    warm = epochs[1:] if len(epochs) > 1 else epochs
    batches = int(counters.get("sample.batches", 0)) / max(len(epochs), 1)
    warm_epoch_s = float(np.median(warm)) if warm else 0.0
    # distributions off the registry histograms (obs/hist) instead of
    # scalar peaks/full-sorts: the depth histogram separates a queue that
    # sat empty (producer-bound) from one that sat full (consumer-bound) —
    # one high-water number cannot
    from neutronstarlite_tpu.obs.hist import LogHistogram

    hists = snap.get("hists") or {}

    def _hq(name):
        d = hists.get(name)
        if not d or not d.get("count"):
            return None
        h = LogHistogram.from_dict(d)
        q = h.quantiles()
        q["max"] = h.max
        q["count"] = h.count
        return q

    jax.clear_caches()
    return {
        "mode": mode,
        "warm_epoch_s": round(warm_epoch_s, 5),
        "batches_per_epoch": int(batches),
        "batches_per_sec": (
            round(batches / warm_epoch_s, 2) if warm_epoch_s > 0 else None
        ),
        "sample_stall_ms_total": counters.get("sample.stall_ms"),
        "sample_stall_ms_dist": _hq("sample.stall_ms"),
        "sample_h2d_ms_total": counters.get("sample.h2d_ms"),
        # fused pins this to exactly 0; sync prices the wire_accounting
        # formula; pipelined/device measure it per staged batch
        "sample_h2d_bytes_total": counters.get("sample.h2d_bytes"),
        # fused: ONE scan dispatch per epoch (sample/fused.py counts
        # them), plus the per-bucket compile count — steady state must
        # show dispatches == epochs and exactly one compile
        "dispatches": counters.get("sample.dispatches"),
        "epoch_compiles": {
            k: int(v) for k, v in counters.items()
            if k.startswith("sample.epoch_compiles.")
        } or None,
        "queue_depth_peak": snap["gauges"].get("sample.queue_depth"),
        "queue_depth_dist": _hq("sample.queue_depth"),
        # full precision: the sync==pipelined parity flag below is a
        # BITWISE claim — rounding would hide exactly the sub-1e-6
        # divergence a pipeline-determinism regression produces
        "loss_history": [float(v) for v in tr.loss_history],
        "final_loss": result["loss"],
        "wall_s": round(wall_s, 2),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=0.02,
                    help="fraction of the Reddit-scale synthetic graph")
    ap.add_argument("--batch-size", type=int, default=512)
    ap.add_argument("--fanout", default="25-10")
    ap.add_argument("--epochs", type=int, default=3)
    ap.add_argument("--modes", default="sync,pipelined",
                    help="comma list of SAMPLE_PIPELINE modes to sweep "
                    "(sync, pipelined, device, fused)")
    ap.add_argument("--precision", default="float32",
                    choices=["float32", "bfloat16"])
    args = ap.parse_args(argv)

    modes = [m.strip() for m in args.modes.split(",") if m.strip()]
    for m in modes:
        if m not in ("sync", "pipelined", "device", "fused"):
            raise SystemExit(f"unknown mode {m!r} in --modes")
    # the env override outranks cfg.sample_pipeline in
    # resolve_sample_pipeline — left set, every leg of this sweep would
    # silently run the SAME mode and the verdict would be meaningless
    if os.environ.pop("NTS_SAMPLE_PIPELINE", None) is not None:
        print(
            "sample_bench: ignoring NTS_SAMPLE_PIPELINE — each --modes "
            "leg selects its own mode", file=sys.stderr,
        )

    import bench  # graph cache + LAYERS/N_LABELS (one source of the workload)

    from neutronstarlite_tpu.utils.platform import (
        configure_compile_cache,
        start_runtime,
    )

    configure_compile_cache()

    cache_dir, v_num, e_num, gen_s = bench.build_and_cache_graph(args.scale)
    host_graph, src, dst = bench.load_cached_graph(cache_dir)

    from neutronstarlite_tpu.graph.dataset import GNNDatum
    from neutronstarlite_tpu.utils.config import InputInfo

    sizes = [int(s) for s in bench.LAYERS.split("-")]
    datum = GNNDatum.random_generate(v_num, sizes[0], bench.N_LABELS, seed=7)

    cfg = InputInfo()
    cfg.algorithm = "GCNSAMPLESINGLE"
    cfg.vertices = v_num
    cfg.layer_string = bench.LAYERS
    cfg.batch_size = args.batch_size
    cfg.fanout_string = args.fanout
    cfg.epochs = args.epochs
    cfg.learn_rate = 0.01
    cfg.weight_decay = 0.0001
    cfg.decay_epoch = -1
    cfg.drop_rate = 0.5
    cfg.precision = args.precision

    os.environ.setdefault("NTS_FINAL_EVAL", "0")
    rows = {
        m: measure_mode(m, cfg, src, dst, datum, host_graph) for m in modes
    }
    start_runtime()  # after the first trainer forked its sampler pool

    head = rows.get("fused") or rows.get("pipelined") or rows[modes[0]]
    sync = rows.get("sync")
    parity = None
    if sync is not None and "pipelined" in rows:
        parity = sync["loss_history"] == rows["pipelined"]["loss_history"]
    # fused draws the SAME distribution with a different (on-device)
    # stream, so its oracle is proximity, not bitwise equality — report
    # the max per-epoch divergence for the caller to judge
    fused_vs_sync = None
    if sync is not None and "fused" in rows:
        fl = rows["fused"]["loss_history"]
        sl = sync["loss_history"]
        if fl and sl and len(fl) == len(sl):
            fused_vs_sync = round(
                max(abs(a - b) for a, b in zip(fl, sl)), 6
            )
    out = {
        "metric": "sample_pipeline_batches_per_sec",
        "value": head["batches_per_sec"],
        "unit": "batches/s",
        "vs_baseline": (
            round(head["batches_per_sec"] / sync["batches_per_sec"], 3)
            if sync and sync["batches_per_sec"] and head["batches_per_sec"]
            else None
        ),
        "extra": {
            "scale": args.scale,
            "v_num": v_num,
            "e_num": e_num,
            "batch_size": args.batch_size,
            "fanout": args.fanout,
            "epochs": args.epochs,
            "modes": rows,
            "sync_pipelined_loss_parity": parity,
            "fused_sync_loss_maxdiff": fused_vs_sync,
            "graph_cache_build_s": round(gen_s, 1),
        },
    }
    # one kind=run row PER MODE into the cross-run perf ledger
    # (NTS_LEDGER_DIR; disabled = no-op): the cfg key embeds the mode so
    # sync/pipelined/device/fused never share a trajectory —
    # perf_sentinel trend-gates warm_median_epoch_s per mode and the
    # batches/s + dispatch counts ride as context
    from neutronstarlite_tpu.obs import ledger

    if ledger.ledger_dir():
        for m, r in rows.items():
            ledger.append_row({
                "kind": "run",
                "ts": time.time(),
                "run_id": f"sample_bench-{m}",
                "algorithm": "GCNSAMPLESINGLE",
                "cfg": f"sample_bench/{m}/B{args.batch_size}/"
                       f"{args.fanout}/s{args.scale}",
                "graph_digest": None,
                "backend": ledger.backend_fingerprint(),
                "epochs": args.epochs,
                "warm_median_epoch_s": r["warm_epoch_s"],
                "avg_epoch_s": r["warm_epoch_s"],
                "sample_stall_ms_per_epoch": (
                    r["sample_stall_ms_total"] / max(args.epochs, 1)
                    if r["sample_stall_ms_total"] is not None else None
                ),
                "sample_h2d_bytes_per_epoch": (
                    r["sample_h2d_bytes_total"] / max(args.epochs, 1)
                    if r["sample_h2d_bytes_total"] is not None else None
                ),
                "batches_per_sec": r["batches_per_sec"],
                "dispatches": r["dispatches"],
                "final_loss": r["final_loss"],
            })
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
