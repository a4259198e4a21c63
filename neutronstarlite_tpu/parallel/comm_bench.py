"""Compare the four distributed aggregation exchanges on the current mesh.

``python -m neutronstarlite_tpu.parallel.comm_bench [--vertices N]
[--avg-degree D] [--feature F] [--partitions P] [--steps K]``

For each comm layer (ring = dense ppermute rotation, ell = all_gather +
gather-only ELL tables, mirror = compacted active-mirror all_to_all,
ring_blocked = the pipelined blocked ring, parallel/dist_ring_blocked.py)
this builds the layout, jits one fused aggregate + backward step, and
reports:

- wire rows/device/layer (the analytic comm volume — what the reference
  tunes with its active-mirror-only messages, comm/network.cpp:505-518);
- peak LIVE exchange-buffer rows/bytes (the memory half of the decision:
  the all_gather family is O(P*vp), the double-buffered rings O(2*vp) —
  tools/wire_accounting.peak_resident_rows);
- measured step time on the current mesh (virtual CPU devices in tests,
  real chips on a pod), plus — for ring_blocked — the per-hop compute
  time of each ring step's stacked tables measured standalone (the
  ``seconds`` the obs ``ring_step`` records leave null in-run).

The GCNDIST trainer's COMM_LAYER:auto heuristic picks mirror vs ring by the
same wire-row comparison printed here; this tool is the measurement that
validates (or overrides) that choice on real hardware.
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np


def bench_layers(v_num, avg_degree, f, partitions, steps, seed=3,
                 kernel_tile=0):
    import jax
    import jax.numpy as jnp

    from neutronstarlite_tpu.graph.storage import build_graph
    from neutronstarlite_tpu.graph.synthetic import synthetic_power_law_graph
    from neutronstarlite_tpu.parallel.dist_blocked import (
        DistBlockedEllPair,
        dist_blocked_gather_dst_from_src,
    )
    from neutronstarlite_tpu.parallel.dist_edge_ops import (
        dist_gather_dst_from_src_mirror,
    )
    from neutronstarlite_tpu.parallel.dist_ell import (
        DistEllPair,
        dist_ell_gather_dst_from_src,
    )
    from neutronstarlite_tpu.parallel.dist_graph import DistGraph
    from neutronstarlite_tpu.parallel.dist_ops import (
        dist_gather_dst_from_src,
        vertex_sharded,
    )
    from neutronstarlite_tpu.parallel.mesh import make_mesh
    from neutronstarlite_tpu.parallel.mirror import MirrorGraph

    e_num = v_num * avg_degree
    src, dst = synthetic_power_law_graph(v_num, e_num, seed=seed)
    g = build_graph(src, dst, v_num, weight="gcn_norm")
    mesh = make_mesh(partitions or None)
    P = mesh.devices.size

    dist = DistGraph.build(g, P)
    mg = MirrorGraph.build(g, P)
    ell = DistEllPair.build(dist).shard(mesh)
    blocks = dist.shard(mesh)
    tables = mg.shard(mesh)

    rng = np.random.default_rng(seed)
    x = vertex_sharded(
        mesh, dist.pad_vertex_array(rng.standard_normal((v_num, f)).astype(np.float32))
    )

    def loss_of(fn):
        def loss(x):
            return (fn(x) ** 2).sum()

        return jax.jit(jax.value_and_grad(loss))

    from neutronstarlite_tpu.parallel.dist_ring_blocked import (
        RingBlockedPair,
        default_ring_vt,
        dist_ring_blocked_gather_dst_from_src,
    )
    from neutronstarlite_tpu.tools.wire_accounting import peak_resident_rows

    ring_vt = default_ring_vt(dist.vp, kernel_tile)
    rblk = RingBlockedPair.build(dist, vt=ring_vt).shard(mesh)

    paths = {
        "ring": (
            loss_of(lambda x: dist_gather_dst_from_src(mesh, blocks, x)),
            (P - 1) * dist.vp,
            peak_resident_rows("ring", P, dist.vp),
        ),
        "ell": (
            loss_of(lambda x: dist_ell_gather_dst_from_src(mesh, ell, x)),
            (P - 1) * dist.vp,  # all_gather ships the same shard rows
            peak_resident_rows("ell", P, dist.vp),
        ),
        "mirror": (
            loss_of(lambda x: dist_gather_dst_from_src_mirror(mesh, mg, tables, x)),
            (P - 1) * mg.mb,  # the p->p all_to_all chunk stays on-device
            peak_resident_rows("mirror", P, dist.vp, mg.mb),
        ),
        "ring_blocked": (
            loss_of(lambda x: dist_ring_blocked_gather_dst_from_src(
                mesh, rblk, x)),
            (P - 1) * dist.vp,  # same total volume, chunked over P-1 hops
            peak_resident_rows("ring_blocked", P, dist.vp),
        ),
    }
    if kernel_tile:
        blk = DistBlockedEllPair.build(dist, vt=kernel_tile).shard(mesh)
        paths["blocked"] = (
            loss_of(lambda x: dist_blocked_gather_dst_from_src(mesh, blk, x)),
            (P - 1) * dist.vp,  # same all_gather wire volume as ell
            peak_resident_rows("blocked", P, dist.vp),
        )

    results = {}
    for name, (fn, wire_rows, peak_rows) in paths.items():
        val, grad = fn(x)  # compile
        jax.block_until_ready(grad)
        t0 = time.time()
        for _ in range(steps):
            val, grad = fn(x)
        jax.block_until_ready(grad)
        dt = (time.time() - t0) / steps
        results[name] = {
            "step_s": round(dt, 5),
            "wire_rows_per_dev_layer": int(wire_rows),
            "wire_mb_per_dev_layer_f32": round(wire_rows * f * 4 / 2**20, 2),
            "peak_live_rows": int(peak_rows),
            "peak_live_mb_f32": round(peak_rows * f * 4 / 2**20, 2),
            "check": float(val),
        }
    results["ring_blocked"]["per_step_compute_s"] = ring_step_times(
        rblk.fwd, f, steps
    )
    results["meta"] = {
        "v_num": v_num, "e_num": int(g.e_num), "feature": f, "P": P,
        "vp": dist.vp, "mb": mg.mb, "eb": dist.eb, "el": mg.el,
        "ring_vt": ring_vt, "ring_work_steps": rblk.fwd.work_steps(),
        "device": str(jax.devices()[0]),
    }
    return results


def bench_edge_family(v_num, avg_degree, f, partitions, steps, seed=3,
                      kernel_tile=0):
    """The attention/edge-family leg (--edge-family): the eager mirror
    GAT chain (one all_to_all + [El, .] edge tensors per layer) vs the
    ring-pipelined fused edge kernel (KERNEL:fused_edge,
    parallel/dist_fused_edge.py), one layer forward+backward each, plus
    the analytic wire rows both ship — the measurement behind the
    fused-vs-eager verdict `metrics_report --diff` gates in
    scripts/ci_tier1.sh."""
    import jax
    import jax.numpy as jnp

    from neutronstarlite_tpu.graph.storage import build_graph
    from neutronstarlite_tpu.graph.synthetic import synthetic_power_law_graph
    from neutronstarlite_tpu.models.gat import LEAKY_SLOPE
    from neutronstarlite_tpu.models.gat_dist import dist_gat_layer
    from neutronstarlite_tpu.parallel.dist_fused_edge import (
        RingFusedEdgePair,
        dist_fused_edge_aggregate,
        fused_wire_cols,
    )
    from neutronstarlite_tpu.parallel.dist_graph import DistGraph
    from neutronstarlite_tpu.parallel.dist_ring_blocked import default_ring_vt
    from neutronstarlite_tpu.parallel.mesh import make_mesh, PARTITION_AXIS
    from neutronstarlite_tpu.parallel.mirror import MirrorGraph
    from jax.sharding import NamedSharding, PartitionSpec as PS

    e_num = v_num * avg_degree
    src, dst = synthetic_power_law_graph(v_num, e_num, seed=seed)
    g = build_graph(src, dst, v_num, weight="ones")
    mesh = make_mesh(partitions or None)
    P = mesh.devices.size

    mg = MirrorGraph.build(g, P)
    tables = mg.shard(mesh)
    dist = DistGraph.build(g, P)
    ring_vt = default_ring_vt(dist.vp, kernel_tile)
    pair = RingFusedEdgePair.build(dist, ring_vt).shard(mesh)

    rng = np.random.default_rng(seed)
    key = rng.standard_normal
    W = jnp.asarray(key((f, f)).astype(np.float32))
    a = jnp.asarray(key((2 * f, 1)).astype(np.float32))

    def put(space, arr):
        return jax.device_put(
            jnp.asarray(space.pad_vertex_array(arr)),
            NamedSharding(mesh, PS(PARTITION_AXIS, None)),
        )

    x_host = key((v_num, f)).astype(np.float32)
    x_mirror = put(mg, x_host)
    x_ring = put(dist, x_host)

    def eager_layer(x):
        return dist_gat_layer(mesh, mg, tables, W, a, x, last=True)

    def fused_layer(x):
        h = x @ W
        al, ar = h @ a[:f], h @ a[f:]
        return dist_fused_edge_aggregate(mesh, pair, h, al, ar, LEAKY_SLOPE)

    def loss_of(fn):
        return jax.jit(jax.value_and_grad(lambda x: (fn(x) ** 2).sum()))

    results = {}
    legs = {
        "mirror_eager_edge": (
            loss_of(eager_layer), x_mirror,
            (P - 1) * mg.mb * (f + 1),  # [h || h.a_src] payload rows
            mg.el * (2 * f + 3) * 4,  # [El, .] edge-tensor bytes/layer
        ),
        "ring_fused_edge": (
            loss_of(fused_layer), x_ring,
            (P - 1) * dist.vp * fused_wire_cols(f, 1)["fwd"],
            0,  # no edge tensors, by construction (jaxpr-pinned in tests)
        ),
    }
    for name, (fn, x, wire_vals, edge_bytes) in legs.items():
        val, grad = fn(x)  # compile
        jax.block_until_ready(grad)
        t0 = time.time()
        for _ in range(steps):
            val, grad = fn(x)
        jax.block_until_ready(grad)
        dt = (time.time() - t0) / steps
        results[name] = {
            "step_s": round(dt, 5),
            "wire_vals_per_dev_layer": int(wire_vals),
            "edge_hbm_bytes_per_layer": int(edge_bytes),
            "check": float(val),
        }
    results["meta"] = {
        "v_num": v_num, "e_num": int(g.e_num), "feature": f, "P": P,
        "vp": dist.vp, "mb": mg.mb, "ring_vt": ring_vt,
        "device": str(jax.devices()[0]),
    }
    return results


def bench_mesh(v_num, avg_degree, f, pv, pf, steps, seed=3, kernel_tile=0,
               side="both", simulate=None):
    """The ``--mesh Pv,Pf`` leg: 1D vertex sharding over Pv*Pf devices vs
    the 2D (vertex x feature) layout (parallel/partitioner.py) on the
    same graph — one jitted exchange fwd+bwd each, plus the analytic
    wire/residency numbers both layouts are priced at
    (tools/wire_accounting.predict_mesh). On the CPU rig (or with
    ``simulate``) each leg times its collective-free sim twin; with a
    reachable mesh the real collectives run (1D ppermute ring vs the 2D
    slab ring + its pad boundary).

    The output is micro_bench-shaped ({"platform", "ops"}) so
    ``metrics_report --diff`` gates it directly: produce side A with
    ``--side 1d`` and side B with ``--side 2d`` — the ``_1d``/``_2d``
    suffixes canonicalize to one shared metric key, exactly the
    fused-edge micro gate pattern."""
    import jax
    import jax.numpy as jnp

    from neutronstarlite_tpu.graph.storage import build_graph
    from neutronstarlite_tpu.graph.synthetic import synthetic_power_law_graph
    from neutronstarlite_tpu.parallel.dist_graph import DistGraph
    from neutronstarlite_tpu.parallel.dist_ring_blocked import (
        RingBlockedPair,
        default_ring_vt,
        dist_ring2d_gather_dst_from_src,
        dist_ring_blocked_gather_dst_from_src,
        dist_ring_blocked_gather_simulated,
    )
    from neutronstarlite_tpu.parallel.mesh import (
        FEATURE_AXIS,
        VERTEX_AXIS,
        make_mesh,
        make_mesh2d,
    )
    from neutronstarlite_tpu.parallel.partitioner import pad_feature_cols
    from neutronstarlite_tpu.tools.wire_accounting import predict_mesh

    P = pv * pf
    if simulate is None:
        simulate = len(jax.devices()) < P
    e_num = v_num * avg_degree
    src, dst = synthetic_power_law_graph(v_num, e_num, seed=seed)
    g = build_graph(src, dst, v_num, weight="gcn_norm")
    rng = np.random.default_rng(seed)

    def loss_of(fn):
        return jax.jit(jax.value_and_grad(lambda x: (fn(x) ** 2).sum()))

    legs = {}
    if side in ("both", "1d"):
        d1 = DistGraph.build(g, P)
        p1 = RingBlockedPair.build(d1, vt=default_ring_vt(d1.vp, kernel_tile))
        xh = d1.pad_vertex_array(
            rng.standard_normal((v_num, f)).astype(np.float32)
        )
        if simulate:
            fn = loss_of(lambda x: dist_ring_blocked_gather_simulated(p1, x))
            x1 = jnp.asarray(xh)
        else:
            from jax.sharding import NamedSharding, PartitionSpec as PS
            from neutronstarlite_tpu.parallel.mesh import PARTITION_AXIS

            m1 = make_mesh(P)
            p1s = p1.shard(m1)
            fn = loss_of(
                lambda x: dist_ring_blocked_gather_dst_from_src(m1, p1s, x)
            )
            x1 = jax.device_put(
                jnp.asarray(xh), NamedSharding(m1, PS(PARTITION_AXIS, None))
            )
        pred1 = predict_mesh(g, P, 1, [f])
        legs["mesh_exchange_1d"] = (fn, x1, pred1)
    if side in ("both", "2d"):
        d2 = DistGraph.build(g, pv)
        p2 = RingBlockedPair.build(d2, vt=default_ring_vt(d2.vp, kernel_tile))
        xh = pad_feature_cols(
            d2.pad_vertex_array(
                rng.standard_normal((v_num, f)).astype(np.float32)
            ),
            pf,
        )
        if simulate:
            fn = loss_of(lambda x: dist_ring_blocked_gather_simulated(p2, x))
            x2 = jnp.asarray(xh)
        else:
            from jax.sharding import NamedSharding, PartitionSpec as PS

            m2 = make_mesh2d(pv, pf)
            p2s = p2.shard(m2, axis=VERTEX_AXIS)
            fn = loss_of(
                lambda x: dist_ring2d_gather_dst_from_src(m2, p2s, x, pf=pf)
            )
            x2 = jax.device_put(
                jnp.asarray(xh),
                NamedSharding(m2, PS(VERTEX_AXIS, FEATURE_AXIS)),
            )
        pred2 = predict_mesh(g, pv, pf, [f])
        legs["mesh_exchange_2d"] = (fn, x2, pred2)

    ops = {}
    for name, (fn, x, pred) in legs.items():
        val, grad = fn(x)  # compile
        jax.block_until_ready(grad)
        t0 = time.time()
        for _ in range(steps):
            val, grad = fn(x)
        jax.block_until_ready(grad)
        ops[name] = {
            "ms": round((time.time() - t0) / steps * 1e3, 4),
            "wire_bytes_per_dev_layer": pred["bytes_per_epoch"],
            "peak_resident_feature_bytes": pred[
                "peak_resident_feature_bytes"
            ],
            "slab_widths": pred["slab_widths"],
            "check": float(val),
        }
    return {
        "platform": str(jax.devices()[0]),
        "ops": ops,
        "meta": {
            "v_num": v_num, "e_num": int(g.e_num), "feature": f,
            "pv": pv, "pf": pf, "simulated": bool(simulate),
        },
    }


def ring_step_times(rbe, f: int, steps: int, seed: int = 5):
    """Per-ring-hop COMPUTE time, measured standalone: one jitted
    aggregate of device 0's stacked tables for each work step over a
    random [vp, f] shard — the honest fill for the ``seconds`` field the
    in-run ``ring_step`` records leave null (one XLA program cannot be
    split per hop from outside)."""
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((rbe.vp, f)).astype(np.float32))
    out = {}
    for s in rbe.work_steps():
        view = rbe._device_step_view(
            [jnp.asarray(n[0]) for n in rbe.nbr[s]],
            [jnp.asarray(w[0]) for w in rbe.wgt[s]],
            [jnp.asarray(d[0]) for d in rbe.dst_row[s]],
        )
        fn = jax.jit(lambda v, view=view: view.aggregate(v))
        jax.block_until_ready(fn(x))  # compile
        t0 = time.time()
        for _ in range(steps):
            r = fn(x)
        jax.block_until_ready(r)
        out[str(s)] = round((time.time() - t0) / steps, 6)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--vertices", type=int, default=20000)
    ap.add_argument("--avg-degree", type=int, default=25)
    ap.add_argument("--feature", type=int, default=128)
    ap.add_argument("--partitions", type=int, default=0)
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument(
        "--kernel-tile", type=int, default=0,
        help="also bench the dist blocked layer (KERNEL_TILE:vt path)",
    )
    ap.add_argument(
        "--edge-family", action="store_true",
        help="bench the attention/edge family instead: eager mirror GAT "
        "chain vs the ring-pipelined fused edge kernel (KERNEL:fused_edge)",
    )
    ap.add_argument(
        "--mesh", default="",
        help="Pv,Pf — bench the 1D layout (Pv*Pf vertex partitions) vs "
        "the 2D (vertex x feature) mesh layout instead (sim twins on the "
        "CPU rig, real collectives when a mesh is reachable); emits "
        "micro_bench-shaped JSON metrics_report --diff can gate",
    )
    ap.add_argument(
        "--side", default="both", choices=("both", "1d", "2d"),
        help="with --mesh: emit one leg only (produce each --diff side "
        "with its own leg so the _1d/_2d suffixes canonicalize to a "
        "shared key)",
    )
    args = ap.parse_args(argv)

    from neutronstarlite_tpu.utils.platform import start_runtime

    start_runtime()
    if args.mesh:
        from neutronstarlite_tpu.parallel.partitioner import MeshSpec

        spec = MeshSpec.parse(args.mesh)
        out = bench_mesh(
            args.vertices, args.avg_degree, args.feature, spec.pv, spec.pf,
            args.steps, kernel_tile=args.kernel_tile, side=args.side,
        )
        # ONE line (the micro_bench convention): metrics_report's --diff
        # side detection parses single-line JSON objects
        print(json.dumps(out))
        return 0
    bench = bench_edge_family if args.edge_family else bench_layers
    out = bench(
        args.vertices, args.avg_degree, args.feature, args.partitions,
        args.steps, kernel_tile=args.kernel_tile,
    )
    print(json.dumps(out, indent=2))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
