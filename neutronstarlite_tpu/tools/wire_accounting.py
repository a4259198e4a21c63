"""Exact per-layer WIRE accounting for the dist exchanges (chip-free).

VERDICT r3 item 7: the comm-layer ranking and the DepCache threshold were
justified by CPU-mesh wall time, which ranks schedules noisily and says
nothing about real ICI. The decisions' actual currency is WIRE VOLUME —
an exact host-side count, no device needed — so this tool prints it and
checks the auto policies against it:

- per-device per-layer RECEIVED remote rows for each comm layer. The
  dense exchanges (ring ppermute rotation, ell/blocked all_gather) each
  deliver P-1 remote shard chunks of vp rows; the mirror all_to_all
  delivers P-1 compacted chunks of Mb rows (the reference's active-only
  message optimization, comm/network.cpp:505-518, as a layout property).
  Mb <= vp always (compaction never grows a chunk), so COMM_LAYER:auto's
  mirror-leaning tie-break is wire-sound; the tool verifies the choice
  equals the wire argmin on the actual graph.
- the DepCache split at a threshold ladder: mc cached (replicated hot
  rows, shipped only on refresh epochs) vs mf fetched per layer, with the
  per-layer amortized wire at refresh cadence R =
  (P-1) * (mf + mc / R) rows — and whether REP_THRESHOLD:auto's choice
  is the wire-minimizing threshold whose cache fits the HBM budget
  (core/NtsScheduler.hpp:556-637 analog).

Usage:
  python -m neutronstarlite_tpu.tools.wire_accounting
      [--scale 1.0 | --cora] [--partitions 8] [--feature 602]
      [--refresh 3] [--budget-mib 256]
Prints ONE JSON line; human-readable table to stderr.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def exchange_rows_per_device(kind: str, P: int, vp: int, mb: int = 0) -> int:
    """Per-device per-layer RECEIVED remote feature rows for one exchange.

    The single formula bridged into the live ``obs`` wire counters (dist
    trainers) AND used by :func:`accounting` below, so the offline report
    and the run-time telemetry can never disagree. Dense exchanges (ring
    ppermute rotation, ell/blocked all_gather, AND the ring-pipelined
    ``ring_blocked`` path) deliver P-1 remote shard chunks of ``vp`` rows
    — ring_blocked ships the SAME total volume as all_gather, chunked
    over P-1 overlapped hops so at most one chunk is in flight (see
    :func:`peak_resident_rows`); the mirror all_to_all delivers P-1
    compacted chunks of ``mb`` rows (the reference's active-only message
    optimization, comm/network.cpp:505-518, as a layout property).
    """
    if P <= 1:
        return 0
    if kind in ("mirror", "mirror_uniform"):
        return (P - 1) * mb
    return (P - 1) * vp


def sample_batch_payload_bytes(node_caps, fanouts) -> int:
    """Bytes of ONE padded SampledBatch device payload — the sampled
    path's per-batch H2D cost the ``sample.h2d_bytes`` counter carries.

    The single formula in three places: the sync trainer loop prices it
    per step, the async producer MEASURES the staged payload
    (sample/pipeline.payload_nbytes — padded capacities are static, so
    measured == priced), and the tuner's sampled-family prior ranks
    modes by it (``SAMPLE_PIPELINE:fused`` ships 0 — the whole batch
    lives on-device). Layout (sample/sampler.py): per-level padded
    int64 node ids at ``node_caps[l]``; per hop ``ecap_h =
    node_caps[h+1] * fanouts[h]`` edges of (int64 src_local, int64
    dst_local, f32 weight); int64 seeds + f32 seed_mask at batch width.
    """
    caps = [int(c) for c in node_caps]
    fo = [int(f) for f in fanouts]
    if len(caps) != len(fo) + 1:
        raise ValueError(
            f"node_caps must be one longer than fanouts, got "
            f"{len(caps)} caps / {len(fo)} fanouts"
        )
    nodes = sum(caps) * 8
    hops = sum(caps[h + 1] * fo[h] * (8 + 8 + 4) for h in range(len(fo)))
    return nodes + hops + caps[-1] * (8 + 4)


def sample_h2d_bytes_per_epoch(n_seeds: int, node_caps, fanouts,
                               mode: str = "sync") -> int:
    """Per-epoch sampled-path H2D bytes for a SAMPLE_PIPELINE mode:
    batches/epoch x the payload formula above for the host-staged modes
    (sync/pipelined/device all ship the same padded payload — the
    pipeline changes WHEN, device mode changes WHERE the draw runs, not
    what crosses the wire), exactly 0 for fused."""
    if mode == "fused":
        return 0
    B = int(node_caps[-1])
    n_batches = -(-int(n_seeds) // max(B, 1))
    return n_batches * sample_batch_payload_bytes(node_caps, fanouts)


def peak_resident_rows(kind: str, P: int, vp: int, mb: int = 0) -> int:
    """Peak EXCHANGE-BUFFER rows live at once per device (the memory half
    of the comm-layer decision; the row count the obs gauge
    ``wire.peak_resident_rows`` carries). The all_gather family
    materializes every shard before compute starts (P*vp); the ring
    families are double-buffered — resident shard + the one in flight
    (2*vp, independent of P); the mirror all_to_all lands all P-1 remote
    compacted chunks plus the resident diagonal (P*mb)."""
    if P <= 1:
        return vp
    if kind in ("mirror", "mirror_uniform"):
        return P * mb
    if kind in ("ring", "ring_blocked"):
        return min(2, P) * vp
    return P * vp


def predict_mesh(g, pv: int, pf: int, widths, itemsize: int = 4,
                 out_widths=None) -> dict:
    """Exact per-device wire/memory prediction for the 2D (vertex x
    feature) mesh layout (parallel/partitioner.py) on one graph:

    - ``bytes_per_epoch``: the vertex RING exchange — (pv-1) hops per
      layer, each shipping a ``[vp, slab_width(w, pf)]`` feature slab.
      This is the quantity the live ``wire.bytes_fwd`` counter carries
      (same ``slab_width`` definition, so live == predicted whenever no
      skip suffix trims the rotation);
    - ``allreduce_bytes_per_epoch``: the feature-axis all-reduce XLA
      inserts where the blocked kernels contract (``agg @ W``): a ring
      all-reduce ships ~``2*(pf-1)/pf`` of each ``[vp, w_out]`` product
      per device per layer. Analytic only — GSPMD owns the collective,
      so no live counter mirrors it; the tune prior prices it so a
      degenerate ``(1, P)`` mesh cannot masquerade as wire-free;
    - ``peak_resident_feature_bytes``: the double-buffered exchange
      residency at slab width — ``min(2, pv) * vp * max(slab) *
      itemsize``, the O(vp*f/Pf) memory claim as a number (the
      ``wire.peak_resident_feature_bytes`` obs gauge).
    """
    from neutronstarlite_tpu.graph.storage import partition_offsets
    from neutronstarlite_tpu.parallel.partitioner import slab_width
    from neutronstarlite_tpu.parallel.vertex_space import round_up

    pv, pf = max(int(pv), 1), max(int(pf), 1)
    offsets = partition_offsets(g.v_num, g.in_degree, pv)
    vp = round_up(int(np.diff(offsets).max()), 8)  # DistGraph.build's rule
    widths = [int(w) for w in widths]
    outs = [int(w) for w in (out_widths if out_widths else widths)]
    slabs = [slab_width(w, pf) for w in widths]
    rows = (pv - 1) * vp
    peak_rows = min(2, pv) * vp
    return {
        "pv": pv, "pf": pf, "vp": int(vp),
        "slab_widths": slabs,
        "exchange_rows": int(rows),
        "bytes_per_epoch": int(rows * sum(slabs) * itemsize),
        "allreduce_bytes_per_epoch": int(
            sum(2 * (pf - 1) * vp * w // pf for w in outs) * itemsize
        ),
        "peak_resident_rows": int(peak_rows),
        "peak_resident_feature_bytes": int(
            peak_rows * (max(slabs) if slabs else 0) * itemsize
        ),
    }


def predict_all(g, P: int, f: int, widths=None, itemsize: int = 4,
                mesh=None) -> dict:
    """Machine-readable per-strategy prediction for one (graph, P, f):
    exchange rows, peak resident rows, and bytes per epoch — the
    autotuner's analytic prior (neutronstarlite_tpu/tune/runner.py) and
    the CLI ``--json`` payload in one function.

    ``widths``: the per-layer exchange widths (defaults to ``[f]`` — one
    exchange per epoch at feature width f); ``itemsize``: wire bytes per
    value (4 = f32, 2 = bf16 wire/compute). All strategies are priced by
    the SAME :func:`exchange_rows_per_device` /
    :func:`peak_resident_rows` formulas the live obs counters use, so the
    prior, the offline report, and the run-time telemetry can never
    disagree. ``mesh=(pv, pf)`` additionally prices the 2D
    (vertex x feature) layout as strategy ``ring2d`` via
    :func:`predict_mesh` (same single-definition slab math as the live
    ``mesh.*`` gauges).
    """
    from neutronstarlite_tpu.parallel.mirror import MirrorGraph, SplitMirror

    mb_uni, vp = MirrorGraph.estimate_mb(g, P)
    mb, _ = SplitMirror.estimate_mb_remote(g, P)
    widths = [int(w) for w in (widths if widths else [f])]
    mbs = {"mirror": mb, "mirror_uniform": mb_uni}
    strategies = {}
    for kind in ("ring", "ell", "blocked", "ring_blocked", "mirror",
                 "mirror_uniform"):
        m = mbs.get(kind, 0)
        rows = exchange_rows_per_device(kind, P, vp, m)
        peak = peak_resident_rows(kind, P, vp, m)
        strategies[kind] = {
            "exchange_rows": int(rows),
            "peak_resident_rows": int(peak),
            "bytes_per_epoch": int(rows * sum(widths) * itemsize),
            "peak_resident_bytes": int(peak * max(widths) * itemsize),
        }
    if mesh is not None:
        pv, pf = (int(x) for x in mesh)
        strategies["ring2d"] = predict_mesh(
            g, pv, pf, widths, itemsize=itemsize
        )
    return {
        "P": int(P), "f": int(f), "vp": int(vp), "mb": int(mb),
        "mb_uniform": int(mb_uni), "widths": widths,
        "itemsize": int(itemsize), "strategies": strategies,
    }


def accounting(g, P: int, f: int, refresh: int, budget_bytes: int,
               thresholds=None) -> dict:
    """All counts are per device per layer unless stated; bytes are f32
    rows (itemsize 4) at feature width f."""
    from neutronstarlite_tpu.parallel.feature_cache import CachedMirrorGraph
    from neutronstarlite_tpu.parallel.mirror import MirrorGraph, SplitMirror

    mb_uni, vp = MirrorGraph.estimate_mb(g, P)
    # the GCN fused path ships the SPLIT exchange since round 5: remote
    # need-sets only (self-loop graphs saturate the uniform mb at vp);
    # the uniform price is kept as a row for the GAT/DepCache chains that
    # still use the [P, P*Mb] layout
    mb, _ = SplitMirror.estimate_mb_remote(g, P)
    dense_rows = exchange_rows_per_device("ring", P, vp)
    mirror_rows = exchange_rows_per_device("mirror", P, vp, mb)
    mirror_uni_rows = exchange_rows_per_device("mirror", P, vp, mb_uni)
    layer_rows = {
        "ring": dense_rows, "ell": dense_rows, "blocked": dense_rows,
        "ring_blocked": dense_rows,
        "mirror": mirror_rows, "mirror_uniform": mirror_uni_rows,
    }
    out = {
        "P": P, "f": f, "vp": vp, "mb": mb, "mb_uniform": mb_uni,
        "layers": layer_rows,
        "bytes_per_layer": {k: v * f * 4 for k, v in layer_rows.items()},
        # wire volume is only half the decision: the ring ships the SAME
        # (P-1)*vp rows as all_gather but holds 2 shard buffers live
        # instead of P — the dist memory envelope argument. Each mirror
        # flavor is priced at ITS OWN slot count (the uniform layout's
        # mb_uni, not the split layout's compacted mb).
        "peak_resident_rows": {
            k: peak_resident_rows(
                k, P, vp,
                {"mirror": mb, "mirror_uniform": mb_uni}.get(k, 0),
            )
            for k in layer_rows
        },
    }
    out["peak_resident_bytes"] = {
        k: v * f * 4 for k, v in out["peak_resident_rows"].items()
    }

    # threshold ladder: degree percentiles of the mirror sources
    if thresholds is None:
        degs = g.out_degree[g.out_degree > 0]
        qs = [50, 75, 90, 99]
        thresholds = sorted(
            {int(np.percentile(degs, q)) for q in qs} | {1}
        )
    ladder = []
    for t in thresholds:
        cm = CachedMirrorGraph.build(g, P, replication_threshold=t)
        amortized = (P - 1) * (cm.mf + cm.mc / max(refresh, 1))
        ladder.append({
            "threshold": t, "mc": cm.mc, "mf": cm.mf,
            "hot_fraction": round(float(cm.cached_fraction), 4),
            "fetch_rows": (P - 1) * cm.mf,
            "amortized_rows": round(amortized, 1),
            "cached_bytes_device": P * cm.mc * f * 4,
        })
    out["depcache"] = ladder

    # --- auto decisions vs the wire argmin --------------------------------
    from neutronstarlite_tpu.parallel.layouts import resolve_comm_layer
    from neutronstarlite_tpu.utils.config import InputInfo

    cfg = InputInfo()
    cfg.comm_layer = "auto"
    auto_choice = resolve_comm_layer(cfg, g, P)
    wire_argmin = min(out["layers"], key=out["layers"].get)
    out["comm_auto"] = {
        "choice": auto_choice,
        "wire_argmin": wire_argmin,
        # mirror and the dense layers tie when compaction saturates
        # (mb == vp); the auto tie-break prefers mirror (one all_to_all
        # vs P-1 dependent rounds) — wire-equivalent, so still sound
        "wire_optimal": out["layers"][auto_choice]
        == out["layers"][wire_argmin],
    }

    t_auto = CachedMirrorGraph.choose_replication_threshold(
        g, P, f, budget_bytes
    )
    cm_auto = CachedMirrorGraph.build(g, P, replication_threshold=t_auto)
    fits = P * cm_auto.mc * f * 4 <= budget_bytes
    # wire-minimality under the budget: no ladder threshold that FITS the
    # budget ships strictly less per-layer wire (smaller mf) than the
    # auto choice — compared by wire, not by threshold value (different
    # thresholds can induce the same hot/cold split)
    smaller_wire_fitting = [
        e for e in ladder
        if e["cached_bytes_device"] <= budget_bytes and e["mf"] < cm_auto.mf
    ]
    out["rep_auto"] = {
        "threshold": t_auto, "mc": cm_auto.mc, "mf": cm_auto.mf,
        "cached_bytes_device": P * cm_auto.mc * f * 4,
        "budget_bytes": budget_bytes,
        "fits": fits,
        "wire_minimal_under_budget": not smaller_wire_fitting,
    }
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=1.0)
    ap.add_argument("--cora", action="store_true")
    ap.add_argument("--partitions", type=int, default=8)
    ap.add_argument("--feature", type=int, default=602)
    ap.add_argument("--refresh", type=int, default=3)
    ap.add_argument("--budget-mib", type=int, default=256)
    ap.add_argument(
        "--mesh", default="",
        help="Pv,Pf — also price the 2D (vertex x feature) mesh layout "
        "(strategy 'ring2d' in the --json payload; predict_mesh)",
    )
    ap.add_argument(
        "--json", action="store_true",
        help="machine-readable mode: print the predict_all() per-strategy "
        "prediction (exchange rows, peak resident rows, bytes/epoch) as "
        "one JSON line and skip the DepCache ladder / auto-policy audit",
    )
    args = ap.parse_args(argv)

    if args.cora:
        from neutronstarlite_tpu.graph.storage import (
            build_graph, load_edges,
        )

        fix = os.path.join(REPO, "tests", "fixtures", "cora")
        src, dst = load_edges(os.path.join(fix, "cora.2708.edge.self"))
        g = build_graph(src, dst, 2708, weight="gcn_norm")
        name = "cora"
    else:
        from bench import build_and_cache_graph, load_cached_graph

        d, v_num, e_num, _ = build_and_cache_graph(args.scale)
        g, _, _ = load_cached_graph(d)
        name = f"reddit_synth_x{args.scale:g}"

    mesh = None
    if args.mesh:
        from neutronstarlite_tpu.parallel.partitioner import MeshSpec

        spec = MeshSpec.parse(args.mesh)
        mesh = (spec.pv, spec.pf)

    if args.json:
        out = predict_all(g, args.partitions, args.feature, mesh=mesh)
        out["graph"] = name
        print(json.dumps(out))
        return 0

    out = accounting(
        g, args.partitions, args.feature, args.refresh,
        args.budget_mib << 20,
    )
    out["graph"] = name
    print(
        "\n".join(
            [f"wire accounting: {name} P={out['P']} f={out['f']} "
             f"vp={out['vp']} mb={out['mb']}"]
            + [f"  {k:14s} {v:>12d} rows/dev/layer "
               f"({out['bytes_per_layer'][k] / 2**20:.1f} MiB wire, "
               f"{out['peak_resident_rows'][k]:>8d} rows "
               f"{out['peak_resident_bytes'][k] / 2**20:.1f} MiB resident)"
               for k, v in out["layers"].items()]
            + [f"  depcache t={e['threshold']:>6d}: mc={e['mc']:>6d} "
               f"mf={e['mf']:>6d} hot={e['hot_fraction']:.3f} "
               f"amortized={e['amortized_rows']:>10.0f} rows/dev/layer"
               for e in out["depcache"]]
            + [f"  comm auto -> {out['comm_auto']['choice']} "
               f"(wire argmin {out['comm_auto']['wire_argmin']}, "
               f"optimal={out['comm_auto']['wire_optimal']})",
               f"  rep auto -> t={out['rep_auto']['threshold']} "
               f"fits={out['rep_auto']['fits']} "
               f"minimal={out['rep_auto']['wire_minimal_under_budget']}"]
        ),
        file=sys.stderr,
    )
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
