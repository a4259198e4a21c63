"""AOT compile-check / capacity planning against a TPU topology — no chips.

``python -m neutronstarlite_tpu.tools.aot_check <file.cfg>
[--topology v5e:2x4]``

Compiles the cfg's FULL jitted train step for the named accelerator
topology via ``jax.experimental.topologies`` (PJRT topology descriptions +
the plugin's compiler — remote or local) and prints one JSON line with the
compile result and the compiled module's memory needs (argument/temp/output
bytes vs HBM). Host-side graph/table construction runs on the CPU backend;
no accelerator is claimed at any point, so this works on a dev box with
zero TPU access — offline capacity planning the reference's
compile-and-run-or-OOM workflow cannot do (its CUDA kernels only fail at
launch time, toolkits/main.cpp:34-199 has no dry-run mode).

Single-mesh models lower with every argument replicated on one topology
device. ``ALGORITHM:GCNDIST`` lowers the real distributed step — the
ppermute ring / all_gather+ELL / mirror all_to_all exchange over a mesh of
all topology devices — by building the sharded program spec directly
(mirroring DistGCNTrainer.build_model, which cannot be reused verbatim
because it device_puts onto the runtime mesh).
"""

from __future__ import annotations

import argparse
import json
import os
import time


def _spec_map(args, rep):
    """Replace every array leaf with a ShapeDtypeStruct on ``rep`` (shared
    by the single-device and sampled AOT cases)."""
    import jax

    def spec(a):
        if hasattr(a, "shape") and hasattr(a, "dtype"):
            return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rep)
        return a

    return jax.tree.map(spec, args)


def _single_device_case(cfg, base_dir, rep):
    """Build the trainer host-side (CPU backend) and return (jitted, args)
    with every leaf replaced by a replicated ShapeDtypeStruct."""
    from neutronstarlite_tpu.models import get_algorithm

    cls = get_algorithm(cfg.algorithm)
    toolkit = cls(cfg, base_dir=base_dir)
    toolkit.init_graph()
    toolkit.init_nn()
    if not hasattr(toolkit, "aot_args"):
        raise SystemExit(
            f"ALGORITHM {cfg.algorithm}: trainer exposes no aot_args() hook"
        )

    return toolkit._train_step, _spec_map(toolkit.aot_args(), rep)


def _synthetic_edges(cfg, scale: float):
    """Reddit-scale synthetic edge list via bench.py's on-disk graph cache
    (numpy only — the cache is shared with the benchmark, so a prior bench
    run makes this instant). Overrides the cfg's EDGE_FILE/VERTICES."""
    import sys as _sys

    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    if repo not in _sys.path:
        _sys.path.insert(0, repo)
    from bench import build_and_cache_graph, load_cached_graph

    d, v_num, _, _ = build_and_cache_graph(scale)
    _, src, dst = load_cached_graph(d)
    cfg.vertices = v_num
    return src, dst


def _dist_gcn_case(cfg, base_dir, mesh, edges=None):
    """The distributed GCN train step as ShapeDtypeStructs over ``mesh``:
    the layout is the one ``parallel/layouts.build_exchange`` gives
    DistGCNTrainer.build_model for the same cfg, left on the host."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as PS

    from neutronstarlite_tpu.graph.storage import build_graph, load_edges
    from neutronstarlite_tpu.models.gcn import init_gcn_params
    from neutronstarlite_tpu.models.gcn_dist import (
        DistGCNTrainer,
        dist_gcn_forward,
    )
    from neutronstarlite_tpu.nn.param import AdamConfig, adam_init, adam_update
    from neutronstarlite_tpu.parallel.layouts import build_exchange
    from neutronstarlite_tpu.parallel.mesh import PARTITION_AXIS

    P = mesh.devices.size
    if edges is None:
        edge_path = cfg.resolve_path(cfg.edge_file, base_dir)
        src, dst = load_edges(edge_path)
    else:
        src, dst = edges
    host_graph = build_graph(src, dst, cfg.vertices, weight="gcn_norm")
    sizes = cfg.layer_sizes()
    plan = build_exchange(cfg, host_graph, mesh=mesh, shard=False)
    layer_kind, wire_dtype = plan.kind, plan.wire_dtype

    vsh = NamedSharding(mesh, PS(PARTITION_AXIS, None))
    vsh1 = NamedSharding(mesh, PS(PARTITION_AXIS))
    rsh = NamedSharding(mesh, PS())

    def bspec(a):
        # block arrays shard over their leading (dst-partition/device) axis
        nd = len(a.shape)
        sh = NamedSharding(mesh, PS(PARTITION_AXIS, *([None] * (nd - 1))))
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh)

    blocks = jax.tree.map(bspec, plan.blocks)
    vp_total = plan.dist.vp * P
    params = init_gcn_params(
        jax.random.PRNGKey(0), sizes, with_bn=DistGCNTrainer.with_bn
    )
    adam_cfg = AdamConfig(
        alpha=cfg.learn_rate,
        weight_decay=cfg.weight_decay,
        decay_rate=cfg.decay_rate,
        decay_epoch=cfg.decay_epoch,
    )
    masked_nll = DistGCNTrainer.masked_nll_loss
    drop_rate = cfg.drop_rate
    # same precision binding as DistGCNTrainer.build_model
    compute_dtype = jnp.bfloat16 if cfg.precision == "bfloat16" else None

    # the trainer's feature argument is the once-aggregated slab, in the
    # compute dtype (DistGCNTrainer._aggregate_input)
    feature_dtype = compute_dtype or jnp.float32

    def train_step(params, opt_state, blocks, feature, label, train01, valid, key):
        def loss_fn(p):
            logits = dist_gcn_forward(
                mesh, blocks, p, feature, valid, key, drop_rate, True,
                compute_dtype=compute_dtype, wire_dtype=wire_dtype,
                input_aggregated=True,
            )
            return masked_nll(logits, label, train01), logits

        (loss, logits), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        params, opt_state = adam_update(params, grads, opt_state, adam_cfg)
        return params, opt_state, loss, logits

    def rspec(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rsh)

    args = (
        jax.tree.map(rspec, params),
        jax.tree.map(rspec, adam_init(params)),
        blocks,
        jax.ShapeDtypeStruct((vp_total, sizes[0]), feature_dtype, sharding=vsh),
        jax.ShapeDtypeStruct((vp_total,), jnp.int32, sharding=vsh1),
        jax.ShapeDtypeStruct((vp_total,), jnp.float32, sharding=vsh1),
        jax.ShapeDtypeStruct((vp_total,), jnp.float32, sharding=vsh1),
        jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=rsh),
    )
    return jax.jit(train_step), args, layer_kind


def _sampled_synthetic_case(cfg, scale: float, rep):
    """The sampled trainer's per-batch train step at full graph scale
    (feature/label tables at [V, f] ride the jit boundary; batch shapes are
    static from FANOUT x BATCH_SIZE). VERDICT r4 item 4: the sampled path
    (reference: core/ntsSampler.hpp:113, toolkits/GCN_CPU_SAMPLE.hpp) had
    no full-scale AOT check."""
    from neutronstarlite_tpu.graph.dataset import GNNDatum
    from neutronstarlite_tpu.models import get_algorithm

    src, dst = _synthetic_edges(cfg, scale)
    sizes = cfg.layer_sizes()
    datum = GNNDatum.random_generate(cfg.vertices, sizes[0], sizes[-1], seed=0)
    cls = get_algorithm(cfg.algorithm)
    toolkit = cls.from_arrays(cfg, src, dst, datum)

    return toolkit._train_step, _spec_map(toolkit.aot_args(), rep)


def _dist_edge_case(cfg, base_dir, mesh, edges=None):
    """The distributed GAT/GGCN train step (the EDGE-SPACE chain: [P, El]
    mirror-CSR tables materialized per layer — the capacity risk VERDICT r4
    item 3 flags; reference chain /root/reference/toolkits/
    GAT_CPU_DIST.hpp:185-211) as ShapeDtypeStructs over ``mesh``. Mirrors
    DistGATTrainer.build_model; kept honest by tests/test_aot_check.py."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as PS

    from neutronstarlite_tpu.graph.storage import build_graph, load_edges
    from neutronstarlite_tpu.models.gat_dist import DistGATTrainer
    from neutronstarlite_tpu.models.gat import init_gat_params
    from neutronstarlite_tpu.models.ggcn import init_ggcn_params
    from neutronstarlite_tpu.models.ggcn_dist import DistGGCNTrainer
    from neutronstarlite_tpu.nn.param import AdamConfig, adam_init, adam_update
    from neutronstarlite_tpu.parallel.mesh import PARTITION_AXIS
    from neutronstarlite_tpu.parallel.mirror import MirrorGraph

    is_ggcn = cfg.algorithm.upper().startswith(("GGCN", "GGNN"))
    cls = DistGGCNTrainer if is_ggcn else DistGATTrainer
    P = mesh.devices.size
    if edges is None:
        src, dst = load_edges(cfg.resolve_path(cfg.edge_file, base_dir))
    else:
        src, dst = edges
    host_graph = build_graph(src, dst, cfg.vertices, weight=cls.weight_mode)
    mg = MirrorGraph.build(host_graph, P)
    sizes = cfg.layer_sizes()

    def tspec(a):
        sh = NamedSharding(mesh, PS(PARTITION_AXIS, *([None] * (a.ndim - 1))))
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh)

    # BOTH edge-chain models compile their chunked + remat'd form (what
    # the trainer builds; un-chunked the chains AOT-measured 76.9 GiB
    # (GGCN) / 14.8 GiB (GAT) at full Reddit —
    # docs/perf_runs/round5/aot_fullscale.log). Only need_ids + chunk
    # tables ship (the trainer's 7-tuple).
    import os as _os

    import numpy as _np

    from neutronstarlite_tpu.parallel.mirror import chunk_edge_list

    ec = int(_os.environ.get("NTS_EDGE_CHUNK", 1_000_000))
    ch = chunk_edge_list(mg, ec)
    probe = _np.zeros((P, ch.dp), _np.int32)
    tables = (tspec(mg.need_ids),) + tuple(
        tspec(t) for t in (ch.slot, ch.dstl, ch.dstr, ch.mask, ch.base)
    ) + (tspec(probe),)
    geo_extra = {"n_chunks": int(ch.slot.shape[1]),
                 "ec": int(ch.slot.shape[2]), "dp": int(ch.dp)}
    params = (
        init_ggcn_params(jax.random.PRNGKey(0), sizes)
        if is_ggcn else init_gat_params(jax.random.PRNGKey(0), sizes)
    )
    adam_cfg = AdamConfig(
        alpha=cfg.learn_rate, weight_decay=cfg.weight_decay,
        decay_rate=cfg.decay_rate, decay_epoch=cfg.decay_epoch,
    )
    # the cfg's precision policy comes pre-bound by the trainer's own
    # classmethod — the tool cannot drift from the shipped program
    forward = cls.bind_forward(cfg)
    masked_nll = cls.masked_nll_loss
    drop_rate = cfg.drop_rate

    def train_step(params, opt_state, tables, feature, label, train01, key):
        def loss_fn(p):
            logits = forward(mesh, mg, tables, p, feature, key, drop_rate, True)
            return masked_nll(logits, label, train01), logits

        (loss, logits), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        params, opt_state = adam_update(params, grads, opt_state, adam_cfg)
        return params, opt_state, loss, logits

    vsh = NamedSharding(mesh, PS(PARTITION_AXIS, None))
    vsh1 = NamedSharding(mesh, PS(PARTITION_AXIS))
    rsh = NamedSharding(mesh, PS())

    def rspec(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rsh)

    pv = mg.vp * P
    args = (
        jax.tree.map(rspec, params),
        jax.tree.map(rspec, adam_init(params)),
        tables,
        jax.ShapeDtypeStruct((pv, sizes[0]), jnp.float32, sharding=vsh),
        jax.ShapeDtypeStruct((pv,), jnp.int32, sharding=vsh1),
        jax.ShapeDtypeStruct((pv,), jnp.float32, sharding=vsh1),
        jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=rsh),
    )
    geo = {"Mb": mg.mb, "El": mg.el, "vp": mg.vp}
    geo.update(geo_extra)
    return jax.jit(train_step), args, geo


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("cfg")
    ap.add_argument(
        "--topology", default="v5e:2x4",
        help="PJRT topology name (e.g. v5e:2x4, v5e:4x4, v4:2x2x2)",
    )
    ap.add_argument(
        "--platform", default="tpu",
        help="PJRT platform for get_topology_desc",
    )
    ap.add_argument(
        "--synthetic-scale", type=float, default=None,
        help="ignore EDGE_FILE and use bench.py's cached Reddit-scale "
        "synthetic graph at this scale (1.0 = full) — full-scale capacity "
        "checks without the dataset on disk (dist algorithms only)",
    )
    args = ap.parse_args(argv)

    # host work runs on the CPU backend UNCONDITIONALLY (even when the
    # environment selects an accelerator platform): this tool's contract is
    # that no accelerator is ever claimed — the topology compile below goes
    # to the compiler, not to chips
    import jax

    jax.config.update("jax_platforms", "cpu")
    # Pallas must emit real Mosaic while tracing on this CPU host — the
    # interpret default would compile the emulation (set at TOOL entry,
    # not inside the reusable _dist_gcn_case: a hidden env mutation there
    # would flip every later pallas call in a shared process)
    os.environ["NTS_PALLAS_FORCE_COMPILED"] = "1"
    from neutronstarlite_tpu.utils.platform import start_runtime

    start_runtime()

    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as PS

    from neutronstarlite_tpu.parallel.mesh import PARTITION_AXIS
    from neutronstarlite_tpu.utils.config import InputInfo

    cfg = InputInfo.read_from_cfg_file(args.cfg)
    base_dir = os.path.dirname(os.path.abspath(args.cfg))
    topo = topologies.get_topology_desc(
        platform=args.platform, topology_name=args.topology
    )
    devices = list(topo.devices)

    out = {
        "cfg": os.path.basename(args.cfg),
        "algorithm": cfg.algorithm,
        "topology": args.topology,
        "devices": len(devices),
    }
    alg = cfg.algorithm.upper()
    EDGE_DIST = (
        "GATCPUDIST", "GATGPUDIST", "GATDIST", "GATCPUDISTOPTM",
        "GGCNDIST", "GGCNCPUDIST", "GGNNDIST",
    )
    t0 = time.time()
    try:
        if alg in ("GCNDIST", "GCNTPUDIST") or alg in EDGE_DIST:
            n = cfg.partitions or len(devices)
            if n > len(devices):
                # ValueError (not SystemExit) so the JSON error contract holds
                raise ValueError(
                    f"PARTITIONS:{n} exceeds the {len(devices)}-device "
                    f"topology {args.topology}"
                )
            mesh = Mesh(np.array(devices[:n]), (PARTITION_AXIS,))
            edges = (
                _synthetic_edges(cfg, args.synthetic_scale)
                if args.synthetic_scale is not None
                else None
            )
            out["vertices"] = cfg.vertices
            if alg in EDGE_DIST:
                jitted, shapes, geo = _dist_edge_case(
                    cfg, base_dir, mesh, edges=edges
                )
                out["comm_layer"] = "mirror-edge"
                out.update(geo)
            else:
                jitted, shapes, layer_kind = _dist_gcn_case(
                    cfg, base_dir, mesh, edges=edges
                )
                out["comm_layer"] = layer_kind
            out["partitions"] = n
        elif alg in ("GCNSAMPLESINGLE", "GCNSAMPLE", "GCNCPUSAMPLE") and (
            args.synthetic_scale is not None
        ):
            # full-scale sampled-trainer capacity: build the trainer over
            # the cached synthetic graph + random datum (shapes are all
            # that reach the compiler)
            mesh1 = Mesh(np.array(devices[:1]), ("one",))
            rep = NamedSharding(mesh1, PS())
            jitted, shapes = _sampled_synthetic_case(
                cfg, args.synthetic_scale, rep
            )
            out["vertices"] = cfg.vertices
        else:
            if args.synthetic_scale is not None:
                raise ValueError(
                    "--synthetic-scale supports dist algorithms and "
                    "GCNSAMPLESINGLE only"
                )
            mesh1 = Mesh(np.array(devices[:1]), ("one",))
            rep = NamedSharding(mesh1, PS())
            jitted, shapes = _single_device_case(cfg, base_dir, rep)
        build_s = time.time() - t0
        t0 = time.time()
        compiled = jitted.lower(*shapes).compile()
        mem = compiled.memory_analysis()
        out.update(
            ok=True,
            build_s=round(build_s, 1),
            compile_s=round(time.time() - t0, 1),
            argument_gib=round(mem.argument_size_in_bytes / 2**30, 3),
            temp_gib=round(mem.temp_size_in_bytes / 2**30, 3),
            output_gib=round(mem.output_size_in_bytes / 2**30, 3),
        )
    except Exception as e:  # noqa: BLE001 — report, don't trace-dump
        out.update(ok=False, error=f"{type(e).__name__}: {str(e)[:500]}")
    print(json.dumps(out))
    return 0 if out.get("ok") else 1


if __name__ == "__main__":
    raise SystemExit(main())
