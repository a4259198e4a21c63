"""The control of a cell's check: the plain reference put in the program's
place, computed in the nearest precision below the one the configuration
states. It has to come out as not correct.

    python3 benchmark/control.py --workload <cell> --seeds 11,12,13 [--rehearse]

Not a cell and not part of a run: the configuration's check module gives
the control's errors (``control(ctx)``) by the measures and at the size of
the check itself, no trainer built and no window measured. Prints one JSON
line a seed, each number beside its limit, and exits 0 only if every seed
failed some limit. On the chip it writes the lines to
chiprun_out/control.<cell>.json; PERF.md section 2 has the readings the
limits are held against.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import run as bench_run
from harness import correct, spec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]

    ctx = bench_run.open_context(args.workload, seeds[0], 0.0, False, args.rehearse, chips=1)
    if ctx is None:
        return 3
    control = spec.config_module(ctx.config, "check").control
    limits = correct.tolerance(ctx.config, ctx.rehearse)
    rows = []
    for seed in seeds:
        ctx.seed = seed
        compared = correct.compare(control(ctx), limits)
        rows.append({"workload": args.workload, "seed": seed, "device": ctx.device,
                     "control_passes": correct.passes(compared),
                     "compared": correct.printable(compared)})
        print(json.dumps(rows[-1]), flush=True)
    if not args.rehearse:
        os.makedirs(os.path.join(spec.REPO, "chiprun_out"), exist_ok=True)
        with open(os.path.join(spec.REPO, "chiprun_out", f"control.{args.workload}.json"), "w") as fh:
            json.dump(rows, fh, indent=1)
    return 1 if any(r["control_passes"] for r in rows) else 0


if __name__ == "__main__":
    sys.exit(main())
