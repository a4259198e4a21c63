"""Mini-batch sampled GCN benchmark at Reddit scale.

The full-batch north star (bench.py) covers one of the reference's two
headline training modes; this tool covers the other — fan-out-sampled
mini-batch training (GCN_CPU_SAMPLE, toolkits/GCN_CPU_SAMPLE.hpp; the
BASELINE.json config list names "GCN_CPU_SAMPLE mini-batch neighbor-sampling
on ogbn-products"). Neither products nor Reddit ships in the reference
checkout (download scripts only, zero egress here), so the graph is the same
Reddit-scale synthetic power-law graph bench.py builds — shared through its
on-disk cache — with GraphSAGE-convention sampling hyperparameters
(batch 512, fanout 25-10) over the reference's Reddit layer widths.

Metrics: median per-batch step time (sample + pad + device step, the
pipeline's steady state) and sampled-edges/sec; epoch time extrapolated to
the full train split. Batches replay ONE compiled program (padded static
shapes) — the number to watch is the steady-state batch rate, which is why
the tool reports it directly instead of only a whole-epoch wall time.

Usage: python -m neutronstarlite_tpu.tools.bench_sample [--scale S]
         [--batch-size 512] [--fanout 25-10] [--batches N]
Prints ONE JSON line: {"metric": "gcn_reddit_sampled_batch_time", ...}
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=1.0)
    ap.add_argument("--batch-size", type=int, default=512)
    ap.add_argument("--fanout", default="25-10")
    ap.add_argument(
        "--batches", type=int, default=60,
        help="timed batches after warmup (one compiled program replays; "
        "steady state needs tens, not an epoch's hundreds)",
    )
    ap.add_argument("--warmup", type=int, default=5)
    ap.add_argument("--precision", default="float32",
                    choices=["float32", "bfloat16"])
    ap.add_argument(
        "--deadline", type=float,
        default=float(os.environ.get("NTS_SAMPLE_DEADLINE_S", 1500)),
        help="hard wall bound: dump stacks and exit 3 (fires before an "
        "external supervisor's kill so diagnostics survive)",
    )
    args = ap.parse_args(argv)

    import bench  # graph cache + LAYERS/N_LABELS (one source of the workload)

    bench.start_watchdog(args.deadline)

    from neutronstarlite_tpu.utils.platform import (
        configure_compile_cache,
        start_runtime,
    )

    configure_compile_cache()

    cache_dir, v_num, e_num, gen_s = bench.build_and_cache_graph(args.scale)
    host_graph, src, dst = bench.load_cached_graph(cache_dir)

    import jax

    from neutronstarlite_tpu.graph.dataset import GNNDatum
    from neutronstarlite_tpu.models.gcn_sample import GCNSampleTrainer, _batch_arrays
    from neutronstarlite_tpu.utils.config import InputInfo

    sizes = [int(s) for s in bench.LAYERS.split("-")]
    datum = GNNDatum.random_generate(v_num, sizes[0], bench.N_LABELS, seed=7)

    cfg = InputInfo()
    cfg.algorithm = "GCNSAMPLESINGLE"
    cfg.vertices = v_num
    cfg.layer_string = bench.LAYERS
    cfg.batch_size = args.batch_size
    cfg.fanout_string = args.fanout
    cfg.epochs = 1
    cfg.learn_rate = 0.01
    cfg.weight_decay = 0.0001
    cfg.decay_epoch = -1
    cfg.drop_rate = 0.5
    cfg.precision = args.precision

    t0 = time.time()
    tr = GCNSampleTrainer.from_arrays(
        cfg, src, dst, datum, host_graph=host_graph
    )
    build_s = time.time() - t0
    start_runtime()  # after the trainer forked its sampler pool

    sampler = tr.samplers[0]
    n_train = len(sampler.seed_nids)
    batches_per_epoch = -(-n_train // args.batch_size)

    # steady-state batch loop: the trainer's own run() loops a full epoch;
    # here we time a bounded number of batches through the SAME compiled
    # train step (tr._train_batch) to get the rate without an epoch's wall
    key = jax.random.PRNGKey(9)
    gen = sampler.sample_epoch()
    times = []
    sample_times = []
    total = args.warmup + args.batches
    loss = None
    for bi in range(total):
        # the whole pipeline is timed — host sampling included (the trainer
        # overlaps sampling with device compute via async dispatch, so the
        # serial sum here is an UPPER bound on real epoch time; the split
        # is reported so the overlap headroom is visible)
        t0 = time.time()
        try:
            b = next(gen)
        except StopIteration:
            gen = sampler.sample_epoch()
            b = next(gen)
        t_sampled = time.time()
        nodes, hops, seed_mask, seeds = _batch_arrays(b)
        bkey = jax.random.fold_in(key, bi)
        tr.params, tr.opt_state, loss = tr._train_batch(
            tr.params, tr.opt_state, tr.feature, tr.label,
            nodes, hops, seed_mask, seeds, bkey,
        )
        jax.block_until_ready(loss)
        times.append(time.time() - t0)
        sample_times.append(t_sampled - t0)

    batch_s = float(np.median(times[args.warmup:]))
    sample_s = float(np.median(sample_times[args.warmup:]))
    # sampled work per batch: padded slot capacities bound it; real edges
    # vary per batch — report capacity (the shape the device executes)
    hop_caps = [int(h.src_local.shape[0]) for h in b.hops]
    slots_per_batch = int(sum(hop_caps))
    out = {
        "metric": "gcn_reddit_sampled_batch_time",
        "value": round(batch_s, 5),
        "unit": "s",
        "vs_baseline": None,  # reference publishes no sampled numbers
        "extra": {
            "scale": args.scale,
            "v_num": v_num,
            "e_num": e_num,
            "layers": bench.LAYERS,
            "batch_size": args.batch_size,
            "fanout": args.fanout,
            "precision": args.precision,
            "batches_timed": args.batches,
            "sample_s_median": round(sample_s, 5),
            "device_pad_s_median": round(batch_s - sample_s, 5),
            "edge_slots_per_batch": slots_per_batch,
            "edge_slots_per_sec": round(slots_per_batch / batch_s, 0),
            "train_seeds": int(n_train),
            "batches_per_epoch": int(batches_per_epoch),
            "epoch_s_extrapolated": round(batch_s * batches_per_epoch, 3),
            "final_loss": float(loss),
            "build_s": round(build_s, 1),
            "graph_cache_build_s": round(gen_s, 1),
            "device": str(jax.devices()[0]),
        },
    }
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
