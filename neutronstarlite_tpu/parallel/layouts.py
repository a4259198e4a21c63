"""Which exchange a partitioned run aggregates over: one decision, here.

``build_exchange(cfg, host_graph, ...)`` is the ONE place a cfg becomes a
partitioned layout for the fuse-op family (GCN / GIN / CommNet dist): it
resolves the mesh, the layer kind, builds the host-side vertex space
(``dist``) and the device tables (``blocks``). Every ``blocks`` value knows
its own exchange: ``blocks.exchange(mesh, v, wire_dtype=, partitioner=)``
aggregates ``v`` ``[P*vp, f]`` across the partitions and returns the same
layout, and ``blocks.describe()`` names the layout. The trainer
(models/gcn_dist.py) and the chip-free compile check (tools/aot_check.py)
both call the builder, so they cannot hold different programs.

The layouts, by what selects them (``PARTITIONS`` devices unless a mesh is
handed in):

=============================  ==============  ===========================
cfg                            kind            blocks
=============================  ==============  ===========================
MESH:Pv,Pf                     ring_blocked    RingBlockedPair on the
                                               partitioner's 2D mesh
DIST_PATH:ring_blocked[_sim]   ring_blocked    RingBlockedPair (``_sim`` or
                                               NTS_DIST_SIMULATE=1: the
                                               collective-free twin)
DIST_PATH:all_gather, or       ell             DistEllPair; KERNEL_TILE:vt
COMM_LAYER:ell, or                             DistBlockedEllPair; PALLAS:1
OPTIM_KERNEL:1                                 DistBspPair
COMM_LAYER:mirror              mirror          SplitMirrorTables
COMM_LAYER:ring                ring            RingBlocks
COMM_LAYER:auto (default)      mirror | ring   the fewer wire rows
=============================  ==============  ===========================

A new layout is one table type with ``exchange`` / ``describe`` / ``shard``
and one line below.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Any, Optional

from neutronstarlite_tpu.parallel.dist_graph import DistGraph
from neutronstarlite_tpu.parallel.mesh import make_mesh
from neutronstarlite_tpu.utils.logging import get_logger
from neutronstarlite_tpu.utils.timing import PhaseTimers

log = get_logger("layouts")


@dataclasses.dataclass
class ExchangePlan:
    """What a partitioned run aggregates over (``build_exchange``)."""

    kind: str  # ring | ell | mirror | ring_blocked (gauge wire.comm_layer)
    mesh: Any  # the device mesh; None = the collective-free sim twin
    partitions: int  # vertex partitions (Pv on a 2D mesh)
    dist: Any  # host-side padded vertex space: DistGraph | SplitMirror
    blocks: Any  # device tables; blocks.exchange(mesh, v, ...)
    wire_dtype: Any = None  # what rides the ring's hops; None = compute dtype
    partitioner: Any = None  # the 2D (vertex x feature) placement, MESH:
    # the level tables' padding_stats where the layout has levels (the
    # dist-ELL's), for ToolkitBase.record_table_stats; else None
    table_stats: Optional[dict] = None


def resolve_comm_layer(cfg, host_graph, P: int) -> str:
    """ring | ell | mirror. Explicit COMM_LAYER wins; OPTIM_KERNEL:1
    keeps its historical meaning (ell); auto compares the per-layer WIRE
    rows of the two dense-feature exchanges — both ship P-1 remote
    chunks per device per layer (the local chunk never crosses the
    interconnect), of vp shard rows (ring) vs Mb compacted mirror rows
    — and picks the smaller: the reference's active-mirror-only message
    optimization (comm/network.cpp:505-518) as a build-time decision.
    mb is priced by SplitMirror.estimate_mb_remote (pass 1 over remote
    edges only, since round 5 the mirror layer never ships the
    resident diagonal), so a ring verdict costs no mirror-table
    build."""
    from neutronstarlite_tpu.parallel.mirror import SplitMirror

    if cfg.comm_layer in ("ring", "ell", "mirror"):
        return cfg.comm_layer
    if cfg.comm_layer not in ("", "auto"):
        raise ValueError(f"unknown COMM_LAYER {cfg.comm_layer!r}")
    if cfg.optim_kernel:
        return "ell"
    if P == 1:
        return "ring"  # degenerate: no wire traffic either way
    mb, vp = SplitMirror.estimate_mb_remote(host_graph, P)
    # tie goes to mirror: at equal wire volume it ships one all_to_all
    # instead of P-1 dependent ppermute rounds (measured faster on the
    # 8-device rig even at mb == vp; see docs/PERF.md comm-layer table)
    choice = "mirror" if mb <= vp else "ring"
    log.info(
        "COMM_LAYER auto -> %s (mirror Mb=%d vs ring vp=%d wire "
        "rows/remote chunk/layer)",
        choice, mb, vp,
    )
    return choice


def _gather_tables(cfg, dist: DistGraph):
    """The all_gather family's host tables: the single chip's three table
    layouts (ops/aggregate.build_tables), stacked per device."""
    if cfg.pallas_kernel:
        # PALLAS:1 -> the rectangular Mosaic bsp kernel per shard over the
        # all_gathered slab, the same fused kernel the single chip runs;
        # KERNEL_TILE sets its src-tile height
        from neutronstarlite_tpu.ops.bsp_ell import DEFAULT_VT
        from neutronstarlite_tpu.parallel.dist_bsp import DistBspPair

        return DistBspPair.build(dist, vt=cfg.kernel_tile or DEFAULT_VT)
    if cfg.kernel_tile > 0:
        # the gathered [P*vp, f] slab outgrows the fast gather regime:
        # source-tiled blocked tables per device
        from neutronstarlite_tpu.parallel.dist_blocked import (
            DistBlockedEllPair,
        )

        return DistBlockedEllPair.build(dist, vt=cfg.kernel_tile)
    from neutronstarlite_tpu.parallel.dist_ell import DistEllPair

    return DistEllPair.build(dist)


def build_exchange(
    cfg,
    host_graph,
    *,
    simulate: bool = False,
    mesh=None,
    shard: bool = True,
    timers: Optional[PhaseTimers] = None,
) -> ExchangePlan:
    """The partitioned layout ``cfg`` asks for over ``host_graph``.

    ``simulate``: the caller's sim-twin switch (NTS_DIST_SIMULATE=1 or a
    pinned trainer attribute); the ring_blocked layouts then build the
    collective-free twin (``mesh`` None), the others have none and take a
    real mesh. ``mesh``: a 1D mesh to build over in place of one made from
    ``PARTITIONS`` (tools/aot_check: a described topology). ``shard=False``
    leaves ``blocks`` as host arrays (nothing is placed on ``mesh``'s
    devices). ``timers``: the host build runs inside its ``dist_graph_build``
    and the tables' inside ``dist_tables_build``.
    """
    from neutronstarlite_tpu.parallel import partitioner as pmod

    timers = timers or PhaseTimers()
    spec = pmod.mesh_spec_of(cfg)
    part = None
    wants_ring = cfg.dist_path in ("ring_blocked", "ring_blocked_sim")
    # the _sim spelling forces the collective-free twin (single-core CI);
    # NTS_DIST_SIMULATE=1 does the same for the bare spelling
    simulate = simulate or cfg.dist_path == "ring_blocked_sim"
    if spec is not None:
        # MESH:Pv,Pf — the 2D (vertex x feature) partitioner places the
        # plane on a (Pv, Pf) mesh: the ring_blocked schedule is the layout
        # it emits ((Pv, 1) is bitwise the 1D ring), with Pf > 1 sharding
        # every exchange/resident buffer down to [vp, f/Pf] slabs
        if mesh is not None:
            raise ValueError(
                f"MESH:{spec.cfg_value()} is placed by the partitioner; it "
                "cannot be built over a mesh handed in"
            )
        pmod.check_mesh_cfg(cfg)
        part = pmod.Partitioner.build(spec, simulate=simulate)
        mesh, P, kind = part.mesh, spec.pv, "ring_blocked"
    elif wants_ring and simulate and mesh is None:
        mesh, P, kind = None, cfg.partitions or 2, "ring_blocked"
    else:
        if mesh is None:
            mesh = make_mesh(cfg.partitions or None)
        P = mesh.devices.size
        if wants_ring:
            kind = "ring_blocked"
        elif cfg.dist_path == "all_gather":
            # explicit opt-out of the ring: the gather-only family
            kind = "ell"
        else:
            kind = resolve_comm_layer(cfg, host_graph, P)
        if kind != "ring_blocked" and (
            cfg.wire_dtype or os.environ.get("NTS_WIRE_DTYPE")
        ):
            # loud, not silent (the PRECISION-typo lesson): a user A/B-ing
            # bf16 wire on the all_gather/mirror paths would otherwise
            # measure an unchanged f32 exchange
            log.warning(
                "WIRE_DTYPE/NTS_WIRE_DTYPE only applies to "
                "DIST_PATH:ring_blocked; the %s exchange ships the "
                "compute dtype (use PRECISION:bfloat16 to narrow it)", kind,
            )

    if kind == "mirror":
        from neutronstarlite_tpu.parallel.mirror import SplitMirror

        with timers.phase("dist_graph_build"):
            dist = SplitMirror.build(host_graph, P)
    else:
        with timers.phase("dist_graph_build"):
            dist = DistGraph.build(
                host_graph, P, edge_chunk=cfg.edge_chunk or None
            )
    wire_dtype = None
    table_stats = None
    note = ""
    with timers.phase("dist_tables_build"):
        over = {}
        if kind == "mirror":
            host = dist.tables()
        elif kind == "ring":
            host = dist.step_blocks()
        elif kind == "ell":
            host = _gather_tables(cfg, dist)
        else:
            from neutronstarlite_tpu.parallel.dist_ring_blocked import (
                RingBlockedPair,
                default_ring_vt,
            )
            from neutronstarlite_tpu.parallel.ring_schedule import (
                resolve_wire_dtype,
            )

            if cfg.pallas_kernel:
                # loud, not silent: the ring's per-step compute is the
                # XLA blocked scan only — there is no Mosaic ring body
                log.warning(
                    "PALLAS:1 ignored: DIST_PATH:ring_blocked runs the "
                    "XLA blocked step tables (no Mosaic ring executor)"
                )
            # KERNEL_TILE caps the per-gather table exactly as on the
            # all_gather blocked path
            host = RingBlockedPair.build(
                dist, vt=default_ring_vt(dist.vp, cfg.kernel_tile)
            )
            wire_dtype = resolve_wire_dtype(cfg.wire_dtype)
            note = f", wire dtype {wire_dtype or 'compute'}"
            if part is not None:
                # a 2D mesh shards the tables over the vertex axis,
                # replicated across the feature axis (every slab runs the
                # schedule)
                over = {"axis": pmod.VERTEX_AXIS}
        if kind in ("ell", "ring_blocked"):
            est = host.padding_stats(dist.padding_stats()["real_edges"])
            note += ", %.2fx/%.2fx fwd/bwd slot padding" % (
                est["fwd_waste_ratio"], est["bwd_waste_ratio"]
            )
            if "levels" in est:
                table_stats = est
        blocks = (
            host.shard(mesh, **over) if shard and mesh is not None else host
        )
    log.info(
        "%s exchange over %d partitions%s: %s%s", kind, P,
        " (sim)" if mesh is None else "", blocks.describe(), note,
    )
    return ExchangePlan(
        kind=kind, mesh=mesh, partitions=P, dist=dist, blocks=blocks,
        wire_dtype=wire_dtype, partitioner=part, table_stats=table_stats,
    )
