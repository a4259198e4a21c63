"""Per-candidate scoring: analytic prior + short jitted timed trials.

Two stages, cheapest first:

1. **Analytic prior** (:func:`analytic_priors`) — a per-candidate byte
   score from ``tools/wire_accounting.predict_all`` (exchange rows, peak
   resident rows) plus the edge-family HBM-traffic estimate the
   ``kernel.edge_hbm_bytes_per_epoch`` gauge already prices: predicted
   exchange bytes per epoch + peak exchange residency + edge-tensor HBM
   round-trips. No device work; SCV-GNN's structure-driven format
   argument as arithmetic. The prior prunes the space to
   ``NTS_TUNE_MAX_TRIALS`` (default 4) candidates before anything is
   timed.

2. **Measured micro-trials** (:func:`measure_candidates`) — one jitted
   forward+backward leg per surviving candidate, comm_bench-style: the
   dense dist exchanges run their real collective over the mesh when one
   is reachable and the collective-free sim twin on the single-core rig
   (the same twin the trainer itself would run there); the edge family
   runs the eager chain vs the fused blocked kernel at the model's
   hidden width and score-channel count. Each leg is timed for
   ``NTS_TUNE_STEPS`` (default 2) steps after one compile step, and the
   warm median is taken via the existing compile-attribution collector
   (``obs/collectors.steady_state_stats``) so the jit compile never
   pollutes the score. A candidate the rig cannot measure (the eager
   mirror chain of a C>1 edge family without a reachable mesh) keeps its
   prior and is recorded as ``source=prior``.

Every scored candidate emits one typed ``tune_trial`` record through the
caller-provided emitter, so the whole tuning episode is reconstructable
from the obs stream alone.
"""

from __future__ import annotations

import os
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from neutronstarlite_tpu.tune.space import AXES, Candidate, _norm
from neutronstarlite_tpu.utils.logging import get_logger

log = get_logger("tune")


def tune_steps() -> int:
    """Timed steps per trial (``NTS_TUNE_STEPS``, default 2, min 1); one
    extra compile step is always run and excluded from the score."""
    raw = os.environ.get("NTS_TUNE_STEPS", "")
    try:
        return max(int(raw), 1) if raw else 2
    except ValueError:
        log.warning("bad NTS_TUNE_STEPS=%r; using 2", raw)
        return 2


def max_trials() -> int:
    """Prior-pruned trial budget (``NTS_TUNE_MAX_TRIALS``, default 4,
    min 1): only the best-prior candidates pay for a measurement."""
    raw = os.environ.get("NTS_TUNE_MAX_TRIALS", "")
    try:
        return max(int(raw), 1) if raw else 4
    except ValueError:
        log.warning("bad NTS_TUNE_MAX_TRIALS=%r; using 4", raw)
        return 4


def _bf16(wire_dtype: str) -> bool:
    return _norm("wire_dtype", wire_dtype) == "bf16"


# ---- stage 1: the analytic prior -------------------------------------------


def _sample_caps(sample_cfg) -> tuple:
    """(batch_size, fanouts, node_caps, n_seeds) from the sampled-family
    leg config — the sampler's capacity recurrence (sample/sampler.py),
    shared by the prior and the micro-trial legs."""
    sc = sample_cfg or {}
    B = int(sc.get("batch_size", 16) or 16)
    fans = [int(x) for x in (sc.get("fanouts") or [])] or [2]
    caps = [B]
    for fo in reversed(fans):
        caps.append(caps[-1] * fo)
    caps = list(reversed(caps))
    return B, fans, caps, int(sc.get("n_seeds", 0) or 0)


def analytic_priors(host_graph, P: int, sizes: List[int], family: str,
                    candidates: List[Candidate], precision: str = "float32",
                    score_channels: int = 1, eager_widths: bool = False,
                    sample_cfg: Optional[dict] = None,
                    input_hoisted: bool = False,
                    ) -> Dict[str, int]:
    """{candidate label: predicted bytes/epoch} — lower is better.

    The score is (exchange bytes per epoch) + (peak exchange-buffer
    residency) + (edge-tensor HBM round-trip bytes per epoch), all from
    the SAME formulas the live obs counters are priced by
    (``wire_accounting.exchange_rows_per_device`` /
    ``peak_resident_rows`` and the ``kernel.edge_hbm_bytes_per_epoch``
    estimate), so the prior can never disagree with the telemetry the
    decision is later judged against.
    """
    from neutronstarlite_tpu.models.gcn_dist import exchange_widths
    from neutronstarlite_tpu.tools.wire_accounting import predict_all

    sizes = [int(s) for s in sizes] or [1]
    widths = exchange_widths(eager_widths, sizes, input_hoisted) or [sizes[0]]
    hidden = sizes[1:] or [sizes[0]]
    base_item = 2 if precision == "bfloat16" else 4
    # ONE predict_all pass at itemsize=1 (its row/peak math is itemsize-
    # independent and its mirror-slot estimates walk all E edges — per-
    # candidate repeats would multiply seconds of host work at scale);
    # each candidate then scales the unit-byte scores by its own itemsize
    unit = None
    if family in ("dist_dense", "edge_dist"):
        unit = predict_all(
            host_graph, P, widths[0],
            widths=(hidden if family == "edge_dist" else widths),
            itemsize=1,
        )["strategies"]
    mesh_units: Dict[str, dict] = {}
    out: Dict[str, int] = {}
    for cand in candidates:
        item = 2 if _bf16(cand.wire_dtype) else base_item
        score = 0
        if family == "dist_dense" and cand.mesh:
            # 2D (vertex x feature) mesh: the ring exchange at slab
            # width + the feature-axis all-reduce XLA inserts at each
            # contraction + the slab-resident double buffer — all from
            # predict_mesh, the same single-definition math the live
            # mesh.* gauges carry. The all-reduce term is what keeps a
            # degenerate (1, P) shape from masquerading as wire-free.
            from neutronstarlite_tpu.tools.wire_accounting import (
                predict_mesh,
            )

            if cand.mesh not in mesh_units:
                pv, pf = (int(t) for t in cand.mesh.split(","))
                mesh_units[cand.mesh] = predict_mesh(
                    host_graph, pv, pf, widths, itemsize=1,
                    out_widths=hidden,
                )
            pred = mesh_units[cand.mesh]
            score = item * pred["bytes_per_epoch"] + base_item * pred[
                "allreduce_bytes_per_epoch"
            ] + item * pred["peak_resident_feature_bytes"]
        elif family == "dist_dense":
            kind = (
                "ell" if cand.dist_path == "all_gather" else "ring_blocked"
            )
            pred = unit[kind]
            score = item * (
                pred["bytes_per_epoch"] + pred["peak_resident_bytes"]
            )
        elif family in ("edge_single", "edge_dist"):
            if family == "edge_dist":
                kind = "ring" if cand.kernel == "fused_edge" else "mirror"
                pred = unit[kind]
                score += base_item * (
                    pred["bytes_per_epoch"] + pred["peak_resident_bytes"]
                )
            if cand.kernel != "fused_edge":
                # the eager chain's [Ep, .] edge-tensor HBM traffic: two
                # feature-wide passes + three score-width passes per layer
                # (the kernel.edge_hbm_bytes_per_epoch gauge formula); the
                # fused kernel pins this to exactly 0 by construction
                e = int(host_graph.e_num)
                score += sum(
                    e * (2 * f + 3 * score_channels) * 4 for f in hidden
                )
        elif family == "sampled":
            # per-epoch sample-payload H2D bytes, the SAME formula the
            # sample.h2d_bytes counter is priced by (wire_accounting.
            # sample_h2d_bytes_per_epoch): sync/pipelined/device all ship
            # every padded batch host->device; fused ships 0 by
            # construction, so the prior prefers it and the trials then
            # arbitrate the host-cost ordering of the other three
            from neutronstarlite_tpu.tools.wire_accounting import (
                sample_h2d_bytes_per_epoch,
            )

            B, fans, caps, n_seeds = _sample_caps(sample_cfg)
            mode = _norm(
                "sample_pipeline", cand.sample_pipeline
            ) or "sync"
            score = sample_h2d_bytes_per_epoch(
                n_seeds or int(host_graph.v_num), caps, fans, mode=mode
            )
        out[cand.label()] = int(score)
    return out


# ---- stage 2: measured micro-trials ----------------------------------------


def _time_leg(fn, steps: int, metrics=None, label: str = "") -> float:
    """Warm-median seconds of ``fn(scale)`` over ``steps`` timed calls
    after one compile call. The scale argument forces a fresh dispatch
    per call (the micro_bench idiom); warm-vs-compile attribution is the
    existing collector's, so the jit compile never rides the score.
    When a registry is passed, the leg's program cost is captured too
    (obs/cost, label ``tune.trial/<candidate>``) so every trial's XLA
    numbers sit next to its prior in the stream."""
    import jax
    import jax.numpy as jnp

    from neutronstarlite_tpu.obs.collectors import steady_state_stats

    jfn = jax.jit(fn)
    if metrics is not None:
        from neutronstarlite_tpu.obs.cost import capture_program_cost

        capture_program_cost(
            metrics, f"tune.trial/{label}", jitted=jfn,
            args=(jnp.float32(1.0),),
        )
    times = []
    for i in range(steps + 1):
        s = jnp.float32(1.0 + 1e-6 * i)
        t0 = time.perf_counter()
        jax.block_until_ready(jfn(s))
        times.append(time.perf_counter() - t0)
    stats = steady_state_stats(times)
    warm = stats["warm_median_s"]
    return float(warm if warm is not None else times[-1])


def _grad_leg(exchange_fn, x):
    """fwd+bwd through one exchange/aggregate: the gradient wrt the fresh-
    dispatch scale backpropagates through the whole leg."""
    import jax

    return jax.value_and_grad(lambda s: (exchange_fn(x * s) ** 2).sum())


def measure_candidates(
    host_graph, P: int, sizes: List[int], family: str,
    candidates: List[Candidate], simulate: bool,
    kernel_tile: int = 0, edge_chunk: int = 0, score_channels: int = 1,
    steps: Optional[int] = None, seed: int = 7, metrics=None,
    sample_cfg: Optional[dict] = None,
) -> Dict[str, Optional[float]]:
    """{candidate label: warm seconds | None (unmeasurable on this rig)}.

    Builds are shared where the layout allows (one DistGraph serves every
    dist candidate); each leg is one jitted fwd+bwd at the widths the
    model actually exchanges.
    """
    import jax
    import jax.numpy as jnp

    steps = steps if steps is not None else tune_steps()
    sizes = [int(s) for s in sizes] or [8]
    rng = np.random.default_rng(seed)
    out: Dict[str, Optional[float]] = {}

    if family == "dist_dense":
        from neutronstarlite_tpu.parallel.dist_graph import DistGraph
        from neutronstarlite_tpu.parallel.dist_ring_blocked import (
            RingBlockedPair,
            default_ring_vt,
            dist_ring_blocked_gather_dst_from_src,
            dist_ring_blocked_gather_simulated,
        )
        from neutronstarlite_tpu.tune.space import mesh_reachable

        f = sizes[0]  # the dominant (input-width) exchange
        # the P-partition 1D rig, built lazily: a space whose every
        # candidate carries a mesh value never partitions over P at all
        _base: list = []

        def base_rig():
            if not _base:
                d = DistGraph.build(
                    host_graph, P, edge_chunk=edge_chunk or None
                )
                _base.append(d)
                _base.append(d.pad_vertex_array(
                    rng.standard_normal(
                        (host_graph.v_num, f)
                    ).astype(np.float32)
                ))
            return _base[0], _base[1]

        mesh = None
        ring_pair = None
        # mesh value -> everything its candidates share (dist, pair,
        # padded input; the real-mesh triple joins lazily) — wire-dtype
        # variants of one shape must time the SAME input and reuse the
        # one O(E) table upload
        mesh_rigs: Dict[str, dict] = {}
        # every dist_dense leg is exchange + ONE contraction at the
        # model's first hidden width: the matmul FLOPs are identical
        # across candidates (same logical math), but a 2D mesh pays its
        # feature-axis all-reduce (real mesh: GSPMD inserts it; sim: the
        # Partitioner.contract slab-partial order) INSIDE the timed leg
        # — without it the degenerate (1, P) shape measures as a
        # zero-hop exchange and wins on seconds while training pays an
        # unmeasured per-layer all-reduce
        h1 = sizes[1] if len(sizes) > 1 else f
        W_c = jnp.asarray(
            rng.standard_normal((f, h1)).astype(np.float32)
        )
        for cand in candidates:
            label = cand.label()
            if cand.mesh:
                # 2D (vertex x feature) candidate: ring over Pv at slab
                # width. The sim leg times the trainer's own twin (full
                # width over Pv — the aggregation is feature-column-
                # independent, so it is the bitwise stand-in); a real
                # rig times the collective 2D exchange on the actual
                # (Pv, Pf) mesh.
                from neutronstarlite_tpu.parallel.dist_ring_blocked import (
                    dist_ring2d_gather_dst_from_src,
                )
                from neutronstarlite_tpu.parallel.partitioner import (
                    MeshSpec,
                    Partitioner,
                    pad_feature_cols,
                )

                pv, pf = (int(t) for t in cand.mesh.split(","))
                if cand.mesh not in mesh_rigs:
                    d2 = DistGraph.build(
                        host_graph, pv, edge_chunk=edge_chunk or None
                    )
                    mesh_rigs[cand.mesh] = {
                        "dist": d2,
                        "pair": RingBlockedPair.build(
                            d2, vt=default_ring_vt(d2.vp, kernel_tile)
                        ),
                        "xh": pad_feature_cols(
                            d2.pad_vertex_array(
                                rng.standard_normal(
                                    (host_graph.v_num, f)
                                ).astype(np.float32)
                            ),
                            pf,
                        ),
                    }
                rig = mesh_rigs[cand.mesh]
                p2, x2h = rig["pair"], rig["xh"]
                wdt = jnp.bfloat16 if _bf16(cand.wire_dtype) else None
                if simulate or not mesh_reachable(pv * pf):
                    con = Partitioner(MeshSpec(pv, pf), mesh=None).contract
                    fn = lambda v, b=p2, w=wdt, c=con: (  # noqa: E731
                        c(dist_ring_blocked_gather_simulated(b, v, w), W_c)
                    )
                    out[label] = _time_leg(
                        _grad_leg(fn, jnp.asarray(x2h)), steps,
                        metrics=metrics, label=label,
                    )
                else:
                    if "mesh" not in rig:
                        from jax.sharding import (
                            NamedSharding,
                            PartitionSpec as PS,
                        )

                        from neutronstarlite_tpu.parallel.mesh import (
                            FEATURE_AXIS,
                            VERTEX_AXIS,
                            make_mesh2d,
                        )

                        rig["mesh"] = make_mesh2d(pv, pf)
                        rig["blocks"] = p2.shard(
                            rig["mesh"], axis=VERTEX_AXIS
                        )
                        rig["x"] = jax.device_put(
                            jnp.asarray(x2h),
                            NamedSharding(
                                rig["mesh"],
                                PS(VERTEX_AXIS, FEATURE_AXIS),
                            ),
                        )
                    con = Partitioner(
                        MeshSpec(pv, pf), mesh=rig["mesh"]
                    ).contract
                    fn = lambda v, m=rig["mesh"], b=rig["blocks"], \
                            w=wdt, q=pf, c=con: (  # noqa: E731
                        c(dist_ring2d_gather_dst_from_src(m, b, v, w, pf=q),
                          W_c)
                    )
                    out[label] = _time_leg(_grad_leg(fn, rig["x"]), steps,
                                          metrics=metrics, label=label)
            elif cand.dist_path == "all_gather":
                if simulate or not mesh_reachable(P):
                    out[label] = None  # no sim twin for the gather family
                    continue
                from neutronstarlite_tpu.parallel.dist_ell import (
                    DistEllPair,
                    dist_ell_gather_dst_from_src,
                )
                from neutronstarlite_tpu.parallel.dist_ops import (
                    vertex_sharded,
                )
                from neutronstarlite_tpu.parallel.mesh import make_mesh

                dist, xh = base_rig()
                mesh = mesh or make_mesh(P)
                ell = DistEllPair.build(dist).shard(mesh)
                x = vertex_sharded(mesh, xh)
                fn = lambda v: (  # noqa: E731,B023
                    dist_ell_gather_dst_from_src(mesh, ell, v) @ W_c
                )
                out[label] = _time_leg(_grad_leg(fn, x), steps,
                                      metrics=metrics, label=label)
            elif _norm("dist_path", cand.dist_path) == "ring_blocked":
                dist, xh = base_rig()
                if ring_pair is None:
                    ring_pair = RingBlockedPair.build(
                        dist, vt=default_ring_vt(dist.vp, kernel_tile)
                    )
                wdt = jnp.bfloat16 if _bf16(cand.wire_dtype) else None
                if simulate or not mesh_reachable(P):
                    blocks, x = ring_pair, jnp.asarray(xh)
                    fn = lambda v, w=wdt: (  # noqa: E731
                        dist_ring_blocked_gather_simulated(blocks, v, w)
                        @ W_c
                    )
                else:
                    from neutronstarlite_tpu.parallel.dist_ops import (
                        vertex_sharded,
                    )
                    from neutronstarlite_tpu.parallel.mesh import make_mesh

                    mesh = mesh or make_mesh(P)
                    blocks = ring_pair.shard(mesh)
                    x = vertex_sharded(mesh, xh)
                    fn = lambda v, b=blocks, w=wdt: (  # noqa: E731
                        dist_ring_blocked_gather_dst_from_src(mesh, b, v, w)
                        @ W_c
                    )
                out[label] = _time_leg(_grad_leg(fn, x), steps,
                                      metrics=metrics, label=label)
            else:
                out[label] = None
        return out

    if family == "edge_single":
        from neutronstarlite_tpu.ops.edge import (
            aggregate_edge_to_dst_weighted,
            edge_softmax,
        )
        from neutronstarlite_tpu.ops.fused_edge import (
            FusedEdgePair,
            fused_edge_attention_aggregate,
        )

        f1 = sizes[1] if len(sizes) > 1 else sizes[0]
        C = int(score_channels)
        v = host_graph.v_num
        h = jnp.asarray(rng.standard_normal((v, f1)).astype(np.float32))
        al = jnp.asarray(rng.standard_normal((v, C)).astype(np.float32))
        ar = jnp.asarray(rng.standard_normal((v, C)).astype(np.float32))
        dg = None
        for cand in candidates:
            label = cand.label()
            if cand.kernel == "fused_edge":
                fep = FusedEdgePair.from_host(
                    host_graph, vt=kernel_tile, levels=cand.ell_levels or ""
                )
                fn = lambda x, fe=fep: fused_edge_attention_aggregate(  # noqa: E731
                    fe, x, al, ar, 0.01
                )
            else:
                if dg is None:
                    from neutronstarlite_tpu.ops.device_graph import (
                        DeviceGraph,
                    )

                    dg = DeviceGraph.from_host(
                        host_graph, edge_chunk=edge_chunk or None
                    )

                def fn(x, g=dg):  # the eager decoupled chain
                    score = jax.nn.leaky_relu(
                        al[g.csc_src] + ar[g.csc_dst], negative_slope=0.01
                    )
                    s = edge_softmax(g, score)
                    return aggregate_edge_to_dst_weighted(g, s, x)

            out[label] = _time_leg(_grad_leg(fn, h), steps,
                                      metrics=metrics, label=label)
        return out

    if family == "edge_dist":
        from neutronstarlite_tpu.parallel.dist_fused_edge import (
            RingFusedEdgePair,
            dist_fused_edge_aggregate,
        )
        from neutronstarlite_tpu.parallel.dist_graph import DistGraph
        from neutronstarlite_tpu.parallel.dist_ring_blocked import (
            default_ring_vt,
        )
        from neutronstarlite_tpu.parallel.mirror import MirrorGraph
        from neutronstarlite_tpu.tune.space import mesh_reachable

        f1 = sizes[1] if len(sizes) > 1 else sizes[0]
        C = int(score_channels)
        mesh = None
        if not simulate and mesh_reachable(P):
            from neutronstarlite_tpu.parallel.mesh import make_mesh

            mesh = make_mesh(P)
        for cand in candidates:
            label = cand.label()
            if cand.kernel == "fused_edge":
                dist = DistGraph.build(host_graph, P,
                                       edge_chunk=edge_chunk or None)
                pair = RingFusedEdgePair.build(
                    dist, default_ring_vt(dist.vp, kernel_tile)
                )
                if mesh is not None:
                    pair = pair.shard(mesh)
                h = _padded(dist, rng, f1, mesh)
                al = _padded(dist, rng, C, mesh)
                ar = _padded(dist, rng, C, mesh)
                fn = lambda x, p=pair, a=al, b=ar: (  # noqa: E731
                    dist_fused_edge_aggregate(mesh, p, x, a, b, 0.01)
                )
                out[label] = _time_leg(_grad_leg(fn, h), steps,
                                      metrics=metrics, label=label)
            elif C == 1:
                # the eager mirror chain trial is the GAT-form layer
                # (models/gat_dist.dist_gat_layer — sim twin when no
                # mesh); the GGCN form (C = f') has no generic leg, so it
                # keeps its prior below
                from neutronstarlite_tpu.models.gat_dist import (
                    dist_gat_layer,
                )

                mg = MirrorGraph.build(host_graph, P)
                tables = mg.shard(mesh) if mesh is not None else None
                f0 = sizes[0]
                W = jnp.asarray(
                    rng.standard_normal((f0, f1)).astype(np.float32)
                )
                a = jnp.asarray(
                    rng.standard_normal((2 * f1, 1)).astype(np.float32)
                )
                h = _padded(mg, rng, f0, mesh)
                fn = lambda x, m=mg, t=tables: (  # noqa: E731
                    dist_gat_layer(mesh, m, t, W, a, x, last=True)
                )
                out[label] = _time_leg(_grad_leg(fn, h), steps,
                                      metrics=metrics, label=label)
            else:
                out[label] = None
        return out

    if family == "sampled":
        return _measure_sampled(
            host_graph, candidates, steps, seed, sample_cfg, metrics
        )

    # plain family: nothing to measure — the space is one empty tuple
    return {cand.label(): None for cand in candidates}


def _measure_sampled(host_graph, candidates: List[Candidate], steps: int,
                     seed: int, sample_cfg: Optional[dict], metrics=None,
                     ) -> Dict[str, Optional[float]]:
    """Per-mode sampling critical path, one batch at the model's real
    (batch_size, fanouts) shape. The legs contain HOST work (that is the
    thing being compared), so timing is hand-rolled over the same
    compile-attribution collector ``_time_leg`` uses instead of a jitted
    scale trick:

    - sync: full host fan-out sample + the padded payload H2D, blocked —
      everything the trainer's batch loop serializes on.
    - pipelined: only the H2D of a pre-sampled payload — the host
      sampling overlaps device compute by construction, so the critical
      path keeps just the staging copy.
    - device: on-device hop draw + host dedup/remap + payload H2D (the
      device_sampler split).
    - fused: ONE dispatch of the jitted on-device sample program
      (sample/fused.py) over the resident tables — no host sampling, no
      payload.
    """
    import jax
    import jax.numpy as jnp

    from neutronstarlite_tpu.obs.collectors import steady_state_stats
    from neutronstarlite_tpu.sample.sampler import Sampler

    B, fans, caps, _ = _sample_caps(sample_cfg)
    v = int(host_graph.v_num)
    seed_ids = np.random.default_rng(seed).integers(
        0, v, size=min(B, v)
    ).astype(np.int64)

    def payload(b):
        return (
            [np.asarray(n) for n in b.nodes],
            [(h.src_local, h.dst_local, h.weight) for h in b.hops],
            b.seed_mask, b.seeds,
        )

    def warm(run) -> float:
        times = []
        for _ in range(steps + 1):
            t0 = time.perf_counter()
            run()
            times.append(time.perf_counter() - t0)
        stats = steady_state_stats(times)
        w = stats["warm_median_s"]
        return float(w if w is not None else times[-1])

    host = Sampler(
        host_graph, np.empty(0, np.int64), B, fans,
        rng=np.random.default_rng(seed),
    )
    device_sampler = None
    out: Dict[str, Optional[float]] = {}
    for cand in candidates:
        label = cand.label()
        mode = _norm("sample_pipeline", cand.sample_pipeline) or "sync"
        if mode == "sync":
            def run_sync(s=host):
                jax.block_until_ready(
                    jax.device_put(payload(s.sample_batch(seed_ids)))
                )

            out[label] = warm(run_sync)
        elif mode == "pipelined":
            staged = payload(host.sample_batch(seed_ids))

            def run_pipe(p=staged):
                jax.block_until_ready(jax.device_put(p))

            out[label] = warm(run_pipe)
        elif mode == "device":
            if device_sampler is None:
                from neutronstarlite_tpu.sample.device_sampler import (
                    DeviceUniformSampler,
                )

                device_sampler = DeviceUniformSampler.from_host(host_graph)
            dsam = Sampler(
                host_graph, np.empty(0, np.int64), B, fans,
                rng=np.random.default_rng(seed),
                hop_sampler=device_sampler,
            )

            def run_dev(s=dsam):
                jax.block_until_ready(
                    jax.device_put(payload(s.sample_batch(seed_ids)))
                )

            out[label] = warm(run_dev)
        elif mode == "fused":
            if device_sampler is None:
                from neutronstarlite_tpu.sample.device_sampler import (
                    DeviceUniformSampler,
                )

                device_sampler = DeviceUniformSampler.from_host(host_graph)
            from neutronstarlite_tpu.sample.fused import (
                degree_tables,
                fused_sample_subgraph,
            )

            out_deg, in_deg = degree_tables(host_graph)
            caps_t, fans_t = tuple(caps), tuple(fans)
            fsf = jax.jit(
                lambda nbr, eff, od, idg, s, n, k: fused_sample_subgraph(
                    nbr, eff, od, idg, s, n, k, caps_t, fans_t
                )
            )
            seeds_pad = np.zeros((B,), np.int32)
            seeds_pad[: len(seed_ids)] = seed_ids
            seeds_dev = jax.device_put(seeds_pad)
            n_real = np.int32(len(seed_ids))
            if metrics is not None:
                from neutronstarlite_tpu.obs.cost import (
                    capture_program_cost,
                )

                capture_program_cost(
                    metrics, f"tune.trial/{label}", jitted=fsf,
                    args=(device_sampler.nbr, device_sampler.eff_deg,
                          out_deg, in_deg, seeds_dev, n_real,
                          jax.random.PRNGKey(0)),
                )
            tick = [0]

            def run_fused(t=tick, nbr=device_sampler.nbr,
                          eff=device_sampler.eff_deg, od=out_deg,
                          idg=in_deg, sd=seeds_dev, nr=n_real):
                t[0] += 1
                jax.block_until_ready(
                    fsf(nbr, eff, od, idg, sd, nr,
                        jax.random.PRNGKey(t[0]))
                )

            out[label] = warm(run_fused)
        else:
            out[label] = None
    return out


def _padded(space, rng, width: int, mesh):
    """A padded vertex-space random array, sharded when a mesh exists."""
    import jax
    import jax.numpy as jnp

    arr = space.pad_vertex_array(
        rng.standard_normal((int(space.v_num), width)).astype(np.float32)
    )
    if mesh is None:
        return jnp.asarray(arr)
    from jax.sharding import NamedSharding, PartitionSpec as PS

    from neutronstarlite_tpu.parallel.mesh import PARTITION_AXIS

    return jax.device_put(
        jnp.asarray(arr), NamedSharding(mesh, PS(PARTITION_AXIS, None))
    )


# ---- orchestration ----------------------------------------------------------


def score_candidates(
    host_graph, P: int, sizes: List[int], family: str,
    candidates: List[Candidate], simulate: bool,
    emit: Optional[Callable[..., Any]] = None,
    measure: bool = True, family_label: Optional[str] = None,
    metrics=None,
    **leg_kwargs,
) -> List[Dict[str, Any]]:
    """Prior + (optionally) measured scores for every candidate, emitted
    as ``tune_trial`` records and returned as a list of
    {candidate, seconds, predicted_bytes, source} dicts (space order
    preserved). Candidates the prior prunes below the trial budget still
    emit (``source=pruned``, prior score only), so the whole episode —
    winners, losers, and never-rans — reconstructs from the obs stream.
    ``family_label`` is the record-facing family string (the tune-space
    family + trainer class, matching the ``tune_decision`` record's);
    ``family`` alone selects the trial legs. With ``measure=False`` no
    device work happens and no records are emitted — the caller is
    deciding from the prior alone (NTS_TUNE=cached miss, or the elastic
    recovery path)."""
    priors = analytic_priors(
        host_graph, P, sizes, family, candidates,
        precision=leg_kwargs.pop("precision", "float32"),
        score_channels=leg_kwargs.get("score_channels", 1),
        eager_widths=leg_kwargs.pop("eager_widths", False),
        sample_cfg=leg_kwargs.get("sample_cfg"),
        input_hoisted=leg_kwargs.pop("input_hoisted", False),
    )
    rows = [
        {"candidate": c.label(), "seconds": None,
         "predicted_bytes": priors[c.label()], "source": "prior"}
        for c in candidates
    ]
    if not measure:
        return rows
    # prior pruning: only the best-prior candidates pay for a trial
    budget = max_trials()
    if len(candidates) > budget:
        keep = {
            r["candidate"]
            for r in sorted(rows, key=lambda r: r["predicted_bytes"])[:budget]
        }
        log.info(
            "tune: prior pruned %d -> %d candidates (NTS_TUNE_MAX_TRIALS)",
            len(candidates), budget,
        )
    else:
        keep = {r["candidate"] for r in rows}
    measured = measure_candidates(
        host_graph, P, sizes, family,
        [c for c in candidates if c.label() in keep], simulate,
        metrics=metrics,
        **leg_kwargs,
    )
    for row in rows:
        secs = measured.get(row["candidate"])
        if secs is not None:
            row["seconds"] = float(secs)
            row["source"] = "measured"
        elif row["candidate"] not in keep:
            row["source"] = "pruned"  # prior cut it below the trial budget
        if emit is not None:
            emit(
                "tune_trial", family=family_label or family,
                candidate=row["candidate"], source=row["source"],
                seconds=row["seconds"],
                predicted_bytes=row["predicted_bytes"], partitions=int(P),
            )
    return rows


def pick_best(rows: List[Dict[str, Any]]) -> Dict[str, Any]:
    """The winning row: smallest measured seconds among measured rows;
    when nothing was measured, smallest prior. Ties break to the earlier
    row (space order — deterministic)."""
    measured = [r for r in rows if r["seconds"] is not None]
    pool = measured or rows
    best = pool[0]
    for r in pool[1:]:
        key = "seconds" if measured else "predicted_bytes"
        if r[key] < best[key]:
            best = r
    return best
