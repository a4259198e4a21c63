"""Workload-matrix benchmark: epoch time for every cfg in configs/.

BASELINE.md's measurement plan is a matrix of per-workload epoch times
(GCN Cora/Citeseer/Pubmed/Reddit, GAT, GIN, CommNet, sampled GCN — the
reference's root *.cfg files). ``bench.py`` owns the north-star
Reddit-scale number; this tool measures the REST of the matrix in one
pass and prints a table plus one JSON line, so every registered model
family has a measured epoch time on the current backend — the analog of
running the reference's run_nts.sh over its cfg set.

Each workload runs in-process (they share one backend init), overriding
EPOCHS to warmup+epochs; the metric is the median post-warmup epoch time
from the trainer's own epoch_times (the reference's per-epoch timers).
Workloads failing to build/run are reported, not fatal.

Usage: python -m neutronstarlite_tpu.tools.bench_matrix [--configs DIR]
       [--epochs N] [--warmup N] [--skip reddit_full]
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import time

import numpy as np


def measure_cfg(cfg_path: str, epochs: int, warmup: int):
    from neutronstarlite_tpu.models import get_algorithm
    from neutronstarlite_tpu.utils.config import InputInfo

    cfg = InputInfo.read_from_cfg_file(cfg_path)
    cfg.epochs = warmup + epochs
    cls = get_algorithm(cfg.algorithm)
    toolkit = cls(cfg, base_dir=os.path.dirname(os.path.abspath(cfg_path)))
    t0 = time.time()
    toolkit.init_graph()
    toolkit.init_nn()
    build_s = time.time() - t0
    result = toolkit.run()
    times = toolkit.epoch_times[warmup:]
    med = float(np.median(times)) if times else None
    return {
        "algorithm": cfg.algorithm,
        "vertices": cfg.vertices,
        "layers": cfg.layer_string,
        "epoch_s": round(med, 5) if med is not None else None,
        "first_epoch_s": round(toolkit.epoch_times[0], 3)
        if toolkit.epoch_times else None,
        "build_s": round(build_s, 2),
        "loss": result.get("loss"),
        "acc_train": (result.get("acc") or {}).get("train"),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--configs", default=os.path.join(
            os.path.dirname(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__)))), "configs",
        ),
    )
    ap.add_argument("--epochs", type=int, default=5)
    ap.add_argument("--warmup", type=int, default=2)
    ap.add_argument(
        "--skip", default="reddit",
        help="comma-separated substrings of cfg names to skip (default: the "
        "reddit workloads — bench.py owns Reddit scale)",
    )
    args = ap.parse_args(argv)

    from neutronstarlite_tpu.utils.platform import (
        configure_compile_cache,
        start_runtime,
    )

    configure_compile_cache()
    skips = [s for s in args.skip.split(",") if s]
    rows = []
    for cfg_path in sorted(glob.glob(os.path.join(args.configs, "*.cfg"))):
        name = os.path.basename(cfg_path)[: -len(".cfg")]
        if any(s in name for s in skips):
            continue
        print(f"== {name}", file=sys.stderr, flush=True)
        try:
            try:
                row = {
                    "workload": name,
                    **measure_cfg(cfg_path, args.epochs, args.warmup),
                }
            except FileNotFoundError:
                # synthesizable dataset not materialized yet: run the prep
                # tool (graph/prep.py, the generate_nts_dataset analog) once
                from neutronstarlite_tpu.graph import prep

                dataset = next(
                    (d for d in prep.DATASETS if d in name), None
                )
                if dataset is None:
                    raise
                base = os.path.dirname(os.path.abspath(cfg_path))
                prep.main(["--dataset", dataset,
                           "--out", os.path.join(base, "..", "data")])
                row = {
                    "workload": name,
                    **measure_cfg(cfg_path, args.epochs, args.warmup),
                }
        except Exception as e:  # a broken workload must not sink the matrix
            row = {"workload": name, "error": f"{type(e).__name__}: {e}"[:200]}
        rows.append(row)
        print(f"   {row}", file=sys.stderr, flush=True)

    device = start_runtime()  # after any sampled trainer forked its pool
    dev = f"{device['platform']} {device['device_kind']} x{device['count']}"
    print(f"\nworkload matrix on {dev} (median of {args.epochs} epochs "
          f"after {args.warmup} warmup):", file=sys.stderr)
    for r in rows:
        if r.get("epoch_s") is not None:
            print(f"  {r['workload']:<22} {r['algorithm']:<18} "
                  f"{r['epoch_s']*1000:9.2f} ms/epoch",
                  file=sys.stderr)
        else:
            print(f"  {r['workload']:<22} FAILED: {r.get('error')}",
                  file=sys.stderr)
    print(json.dumps({"device": dev, "rows": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
