"""Find the highest rate the serving cell sustains, once, on the chip.

    python3 benchmark/knee_sweep.py --workload <serving cell> --seed 1 \
        --rates 250,500,1000,1500,2000,4000,8000 --seconds 6
    python3 benchmark/knee_sweep.py --workload <serving cell> --seed 11 \
        --rates 440,550,660,770,880 --seconds 15 --repeat 3

Not a cell: builds the cell's server once and offers its mix at each rate
in turn, open loop, for ``--seconds`` each. A rate is sustained when no
request is refused or lost, at least 99% of what was offered is answered
within the window plus a second, and the latency of the last quarter of
the requests is no worse than twice that of the first (no growing
backlog). The knee is the highest sustained rate; a cell's traffic file
fixes its rate at a share of it, as a number, and says which. The second
form (``--repeat``: several windows a rate, each with the next seed) shows
how far each percentile swings from window to window at rates under the
knee; both tables are in PERF.md, section 4. Prints one JSON line per
window and writes them to chiprun_out/knee_sweep.json.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

import run as bench_run
from harness import spec, stats


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rates", required=True, help="comma-separated requests per second")
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--repeat", type=int, default=1,
                    help="windows per rate, each with the next seed: how far a tail swings")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    ctx = bench_run.open_context(args.workload, args.seed, args.seconds, False, args.rehearse)
    if ctx is None:
        return 3
    device = ctx.device
    mix = ctx.traffic
    serve_cell = spec.named_module("kinds", "open_loop")
    _, _, engine, server, vertices = serve_cell.build(ctx)
    rows = []
    try:
        serve_cell.warm_up(server, mix, vertices, args.seed)
        windows = [(float(r), args.seed + k) for r in args.rates.split(",")
                   for k in range(args.repeat)]
        for rate, seed in windows:
            rec = serve_cell.offer(server, engine, mix, vertices, seed, args.seconds, rate)
            lat = rec["latency_ms"]
            quarter = max(len(lat) // 4, 1)
            first, last = stats.median(lat[:quarter]), stats.median(lat[-quarter:])
            drained_s = rec["window"][1] - rec["window"][0]
            row = {
                "offered_rps": rate,
                "seed": seed,
                "attempted": rec["attempted"],
                "failed": rec["failed"],
                "answered_rps": (rec["attempted"] - rec["failed"]) / max(drained_s, args.seconds),
                "p50_ms": stats.percentile(lat, 50), "p90_ms": stats.percentile(lat, 90),
                "p95_ms": stats.percentile(lat, 95), "p99_ms": stats.percentile(lat, 99),
                "first_quarter_p50_ms": first, "last_quarter_p50_ms": last,
                "drain_after_window_s": drained_s - args.seconds,
                "late_p99_ms": stats.percentile(rec["late_ms"], 99),
                "queue_p50_ms": float(np.nanmedian(rec["queue_ms"])),
                "seeds_per_flush": rec["counters"]["serve.computed_seeds"]
                / max(rec["counters"]["serve.batches"], 1),
                "device": device,
            }
            row["sustained"] = bool(
                row["failed"] == 0 and row["drain_after_window_s"] < 1.0
                and last <= 2.0 * first
            )
            rows.append(row)
            print(json.dumps(row), flush=True)
    finally:
        server.close()
    if not args.rehearse:
        os.makedirs(os.path.join(spec.REPO, "chiprun_out"), exist_ok=True)
        with open(os.path.join(spec.REPO, "chiprun_out", "knee_sweep.json"), "w") as fh:
            json.dump(rows, fh, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
