"""Ring-pipelined distributed blocked aggregation: overlap ICI with compute.

The reference's signature distributed optimization is the ring-ordered
master->mirror exchange overlapped with per-source-partition aggregation
(core/graph.hpp:2644 sync_compute_decoupled, :3640 GPU dispatch): at ring
step s each rank computes on the shard it HOLDS while the next shard is
already in flight. Our fast dist paths (parallel/dist_ell.py,
dist_blocked.py, dist_bsp.py) traded that schedule for one monolithic
``all_gather`` — a bulk-synchronous barrier that materializes the full
[P*vp, f] feature slab on EVERY device before any compute starts: zero
comm/compute overlap and per-device exchange memory that grows linearly
with the mesh.

This module recovers the paper's design on TPU without giving up the
blocked-kernel compute:

- the per-device adjacency is split BY SOURCE PARTITION into P step
  tables — step s holds the BlockedEll (ops/blocked_ell.py) sub-tables
  whose sources live in the shard resident at that step, with
  shard-LOCAL source ids, so every gather indexes a [vp, f] buffer;
- the shard_map ring body is double-buffered: at step s the resident
  [vp, f] shard is ``ppermute``d to the next neighbor FIRST (XLA's async
  collective-permute start/done lets the ICI transfer fly) and the same
  shard is aggregated through step s's blocked tables while it travels;
- the accumulator is a single [vp, f] f32 carry across ALL steps
  (BlockedEll.aggregate_into), so the exchange dtype never rounds the
  cross-partition sum — WIRE_DTYPE:bf16 (parallel/ring_schedule.py)
  halves ICI bytes with the same accumulation;
- the backward is the REVERSE ring over the transposed step tables
  (gradient push, graph.hpp:3456 compute_sync_decoupled), paired by
  custom_vjp exactly like ops/blocked_ell._blocked_aggregate_bwd;
- a STATIC skip schedule: a step whose block tables are empty on every
  device (an empty partition pair) is dropped from the work list at
  trace time, and a skipped SUFFIX also drops its rotation hops
  (ring_schedule.trim_transfers).

Memory envelope: the exchange holds at most TWO shard buffers live
(resident + in-flight) plus the accumulator — O(2*vp*f) per device
instead of the all_gather's O(P*vp*f). The total wire volume is the same
(P-1)*vp rows per device per layer; it is simply chunked and overlapped.

Enable with ``DIST_PATH:ring_blocked`` on the fuse-op dist trainers
(models/gcn_dist.py family); ``DIST_PATH:ring_blocked_sim`` (or
NTS_DIST_SIMULATE=1) selects the collective-free twin below.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as PS

from neutronstarlite_tpu.ops.blocked_ell import BlockedEll
from neutronstarlite_tpu.parallel.dist_graph import DistGraph
from neutronstarlite_tpu.parallel.mesh import PARTITION_AXIS, shard_map
from neutronstarlite_tpu.parallel.ring_schedule import (
    ring_perm,
    ring_source,
    trim_transfers,
)
from neutronstarlite_tpu.utils.logging import get_logger

log = get_logger("dist_ring_blocked")


def _block_adjacency(own: np.ndarray, nbr: np.ndarray, w: np.ndarray, vp: int):
    """CSC-style (offsets, adj, weights) over ``vp`` destination rows from
    one (dst partition, src partition) edge block — both id spaces are
    partition-local."""
    order = np.argsort(own, kind="stable")
    own, nbr, w = own[order], nbr[order], w[order]
    deg = np.bincount(own, minlength=vp)
    offsets = np.concatenate([[0], np.cumsum(deg)])
    return offsets, nbr, w


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class RingBlockedEll:
    """Per-ring-step stacked blocked tables, one direction.

    ``nbr[s]`` is step s's level list — per level a [P, T, N_l, K] array
    whose row p is device p's tile-local source ids into the shard it
    holds at step s (``ring_source(p, s)``); ``wgt[s]``/``dst_row[s]``
    mirror ops/blocked_ell.BlockedEll (padding rows carry ``dst = vp``
    and weight 0). A step with NO edges anywhere keeps an empty level
    list — the static skip schedule."""

    nbr: List[List[jax.Array]]
    wgt: List[List[jax.Array]]
    dst_row: List[List[jax.Array]]
    partitions: int = dataclasses.field(metadata=dict(static=True))
    vp: int = dataclasses.field(metadata=dict(static=True))
    vt: int = dataclasses.field(metadata=dict(static=True))
    n_tiles: int = dataclasses.field(metadata=dict(static=True))
    # +1 = forward rotation, -1 = the reverse (gradient-push) ring
    direction: int = dataclasses.field(default=1, metadata=dict(static=True))

    @staticmethod
    def build(
        dist: DistGraph, vt: int, transpose: bool = False, direction: int = 1
    ) -> "RingBlockedEll":
        P, vp = dist.partitions, dist.vp
        n_tiles = -(-vp // vt)
        slot = np.arange(dist.eb)
        step_nbr: List[List[jax.Array]] = []
        step_wgt: List[List[jax.Array]] = []
        step_dst: List[List[jax.Array]] = []
        for s in range(P):
            dev_levels: List[dict] = []
            all_k: set = set()
            for p in range(P):
                q = ring_source(p, s, P, direction)
                # realness from the block's explicit edge count (blocks are
                # front-packed) — a legitimate weight-0 edge must survive
                if transpose:
                    # device p owns the src side: edges in block (q, p),
                    # rows = p-local src ids, sources = q-local dst ids
                    real = slot < dist.block_count[q, p]
                    own = dist.block_src[q, p][real].astype(np.int64)
                    nb = dist.block_dst[q, p][real].astype(np.int64)
                    w = dist.block_weight[q, p][real]
                else:
                    # device p owns the dst side: edges in block (p, q)
                    real = slot < dist.block_count[p, q]
                    own = dist.block_dst[p, q][real].astype(np.int64)
                    nb = dist.block_src[p, q][real].astype(np.int64)
                    w = dist.block_weight[p, q][real]
                offsets, nb, w = _block_adjacency(own, nb, w, vp)
                b = BlockedEll.build(
                    vp, offsets, nb, w, vt, src_num=vp, log_stats=False
                )
                by_k = {
                    int(b.nbr[l].shape[-1]): (
                        np.asarray(b.nbr[l]), np.asarray(b.wgt[l]),
                        np.asarray(b.dst_row[l]),
                    )
                    for l in range(len(b.nbr))
                }
                dev_levels.append(by_k)
                all_k.update(by_k)

            nbrs, wgts, dsts = [], [], []
            for K in sorted(all_k):
                n_l = max(
                    by_k[K][0].shape[1] if K in by_k else 0
                    for by_k in dev_levels
                )
                nbr = np.zeros((P, n_tiles, n_l, K), dtype=np.int32)
                wgt = np.zeros((P, n_tiles, n_l, K), dtype=np.float32)
                dstr = np.full((P, n_tiles, n_l), vp, dtype=np.int32)
                for p, by_k in enumerate(dev_levels):
                    if K not in by_k:
                        continue
                    n, w, d = by_k[K]
                    nbr[p, :, : n.shape[1]] = n
                    wgt[p, :, : w.shape[1]] = w
                    dstr[p, :, : d.shape[1]] = d
                # host arrays: shard() sends each device its own slice
                nbrs.append(nbr)
                wgts.append(wgt)
                dsts.append(dstr)
            step_nbr.append(nbrs)
            step_wgt.append(wgts)
            step_dst.append(dsts)

        rbe = RingBlockedEll(
            nbr=step_nbr, wgt=step_wgt, dst_row=step_dst,
            partitions=P, vp=vp, vt=int(vt), n_tiles=int(n_tiles),
            direction=int(direction),
        )
        work = rbe.work_steps()
        log.info(
            "ring-blocked%s: P=%d vp=%d vt=%d (%d tiles), %d work steps / "
            "%d skipped (empty partition pairs), %d rotation hops, "
            "%d table slots",
            " (transposed)" if transpose else "", P, vp, vt, n_tiles,
            len(work), P - len(work), trim_transfers(work),
            rbe.slot_count(),
        )
        return rbe

    # ---- static schedule facts -------------------------------------------
    def work_steps(self) -> List[int]:
        """Steps with any compute anywhere on the mesh (trace-time static:
        derived from the level-list STRUCTURE, not array values)."""
        return [s for s in range(self.partitions) if self.nbr[s]]

    def skipped_steps(self) -> List[int]:
        return [s for s in range(self.partitions) if not self.nbr[s]]

    def n_transfers(self) -> int:
        """ppermute hops per application (skipped suffix trimmed)."""
        return trim_transfers(self.work_steps())

    def slot_count(self) -> int:
        import math

        return sum(
            int(math.prod(n.shape)) for levels in self.nbr for n in levels
        )

    def shard(self, mesh: Mesh, axis: str = PARTITION_AXIS) -> "RingBlockedEll":
        """``axis`` is the mesh axis the step tables shard over: the 1D
        ``p`` axis, or the 2D partitioner's vertex axis (the tables are
        then REPLICATED over the feature axis — every feature slab runs
        the same schedule)."""
        from jax.sharding import NamedSharding

        def put(a):
            spec = PS(axis, *([None] * (a.ndim - 1)))
            return jax.device_put(a, NamedSharding(mesh, spec))

        return RingBlockedEll(
            nbr=[[put(a) for a in levels] for levels in self.nbr],
            wgt=[[put(a) for a in levels] for levels in self.wgt],
            dst_row=[[put(a) for a in levels] for levels in self.dst_row],
            partitions=self.partitions, vp=self.vp, vt=self.vt,
            n_tiles=self.n_tiles, direction=self.direction,
        )

    def _device_step_view(self, nbrs, wgts, dsts) -> BlockedEll:
        """One device's tables for one step (leading P axis sliced away) as
        a square [vp -> vp] BlockedEll, so the SAME aggregate body runs."""
        return BlockedEll(
            nbr=list(nbrs), wgt=list(wgts), dst_row=list(dsts),
            vt=self.vt, v_num=self.vp, n_tiles=self.n_tiles,
            src_num=self.vp,
        )


def default_ring_vt(vp: int, kernel_tile: int = 0) -> int:
    """The ring's source-tile height: KERNEL_TILE when set, else whole-
    shard-ish tiles capped at 512 rows. ONE definition shared by the
    trainer (models/gcn_dist.py) and comm_bench, so the bench always
    measures the blocked layout production runs ship."""
    return kernel_tile or min(vp, 512)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class RingBlockedPair:
    """Forward ring + reverse (transposed) ring; ``shard(mesh)`` first."""

    fwd: RingBlockedEll
    bwd: RingBlockedEll

    @staticmethod
    def build(dist: DistGraph, vt: int) -> "RingBlockedPair":
        return RingBlockedPair(
            fwd=RingBlockedEll.build(dist, vt, transpose=False, direction=1),
            bwd=RingBlockedEll.build(dist, vt, transpose=True, direction=-1),
        )

    def padding_stats(self, real_edges: int) -> dict:
        fwd, bwd = self.fwd.slot_count(), self.bwd.slot_count()
        return {
            "real_edges": int(real_edges),
            "fwd_slots": fwd,
            "bwd_slots": bwd,
            "fwd_waste_ratio": fwd / max(real_edges, 1),
            "bwd_waste_ratio": bwd / max(real_edges, 1),
        }

    def shard(self, mesh: Mesh, axis: str = PARTITION_AXIS) -> "RingBlockedPair":
        return RingBlockedPair(
            fwd=self.fwd.shard(mesh, axis), bwd=self.bwd.shard(mesh, axis)
        )

    def exchange(self, mesh, x: jax.Array, wire_dtype=None,
                 partitioner=None) -> jax.Array:
        """``mesh=None`` is the collective-free sim twin — also the 2D
        layout's exchange twin: the aggregation is feature-column-
        independent, so the full-width sim IS bitwise the slab-sharded
        collective ring (the 2D-specific math, the contraction's
        partial-sum order, lives in partitioner.contract)."""
        if mesh is None:
            return dist_ring_blocked_gather_simulated(self, x, wire_dtype)
        if partitioner is not None:
            # the partitioner's 2D (vertex x feature) mesh: the ring
            # rotates over the vertex axis while each device works a
            # [vp, f/Pf] feature slab (parallel/partitioner.py)
            return dist_ring2d_gather_dst_from_src(
                mesh, self, x, wire_dtype, pf=partitioner.pf
            )
        return dist_ring_blocked_gather_dst_from_src(mesh, self, x, wire_dtype)

    def describe(self) -> str:
        return (
            f"double-buffered ring (vt={self.fwd.vt}, "
            f"{len(self.fwd.work_steps())}/{self.fwd.partitions} work "
            f"steps, {self.fwd.n_transfers()} hops)"
        )


def _flatten_tables(rbe: RingBlockedEll, axis: str = PARTITION_AXIS):
    """(flat array list, in_specs, per-step level counts) — the shard_map
    argument layout; the body re-groups by the static count list."""
    flat, specs = [], []
    for s in range(rbe.partitions):
        for a in (*rbe.nbr[s], *rbe.wgt[s], *rbe.dst_row[s]):
            flat.append(a)
            specs.append(PS(axis, *([None] * (a.ndim - 1))))
    counts = [len(rbe.nbr[s]) for s in range(rbe.partitions)]
    return flat, specs, counts


def _regroup_tables(tables, counts, P):
    """Invert _flatten_tables' layout into {step: (nbr, wgt, dst_row)
    level lists} inside the shard_map body (the leading sharded axis is
    sliced away here). ONE definition shared by the blocked ring and the
    fused edge ring — the two layouts must stay in lockstep."""
    per_step = {}
    i = 0
    for s in range(P):
        c = counts[s]
        if c:
            per_step[s] = (
                [a[0] for a in tables[i : i + c]],
                [a[0] for a in tables[i + c : i + 2 * c]],
                [a[0] for a in tables[i + 2 * c : i + 3 * c]],
            )
        i += 3 * c
    return per_step


def _ring_blocked_apply(
    mesh: Mesh, rbe: RingBlockedEll, x: jax.Array,
    wire_dtype: Optional[jnp.dtype] = None, mode: str = "full",
    axes: tuple = (PARTITION_AXIS, None),
) -> jax.Array:
    """The double-buffered shard_map ring (one direction).

    ``mode`` isolates the two halves of the overlapped schedule for the
    overlap-efficiency probe (``measure_overlap``): ``compute_only`` runs
    every step's blocked tables against the resident shard (identical
    table work, zero hops), ``exchange_only`` runs the bare ppermute hop
    chain (returning the final in-flight buffer so XLA cannot drop the
    dependent chain). ``full`` is the production overlapped body.

    ``axes = (vertex_axis, feature_axis)``: the mesh axis the ring
    rotates over, and the axis ``x``'s feature columns shard over —
    ``None`` on the 1D mesh (features replicated, today's layout),
    the partitioner's feature axis on a 2D mesh, where the IDENTICAL
    body runs per feature slab (the aggregation is feature-column-
    independent) and every buffer inside the body is ``[vp, f/Pf]`` —
    the hop ships a slab, never the full width."""
    vertex_axis, feature_axis = axes
    P = rbe.partitions
    perm = ring_perm(P, rbe.direction)
    n_hops = rbe.n_transfers()
    flat, specs, counts = _flatten_tables(rbe, vertex_axis)

    def body(*args):
        xs = args[-1]
        tables = args[:-1]
        per_step = _regroup_tables(tables, counts, P)
        # ONE f32 accumulator across all steps — per-step results never
        # round in the wire/compute dtype (the r5 ring-body policy)
        acc = jnp.zeros((rbe.vp, xs.shape[1]), jnp.float32)
        cur = xs
        for s in range(P):
            send = s < n_hops and mode != "compute_only"
            # issue the hop FIRST: the async collective-permute can fly
            # over ICI while this step's blocked aggregation consumes the
            # same resident buffer (double buffering — cur stays live
            # until the hop lands in nxt). The wire cast happens on the
            # SHIPPED buffer only: the device's own step-0 shard never
            # rides the ICI and keeps full precision, so each row rounds
            # exactly once — when first shipped (re-casts are identity).
            if send:
                sent = cur if wire_dtype is None else cur.astype(wire_dtype)
                nxt = lax.ppermute(sent, vertex_axis, perm)
            if mode != "exchange_only" and s in per_step:
                view = rbe._device_step_view(*per_step[s])
                # s>0 table work always consumes a wire-dtype buffer: in
                # full mode cur already rounded when first shipped, and
                # compute_only must mirror that (no-op cast there being
                # the resident shard) or the probe's compute_s is biased
                # against a different input dtype than production
                inp = (
                    cur if wire_dtype is None or s == 0
                    else cur.astype(wire_dtype)
                )
                acc = view.aggregate_into(acc, inp)
            if send:
                cur = nxt
        if mode == "exchange_only":
            return cur.astype(xs.dtype)
        return acc.astype(xs.dtype)

    fn = shard_map(
        body,
        mesh=mesh,
        in_specs=tuple(specs) + (PS(vertex_axis, feature_axis),),
        out_specs=PS(vertex_axis, feature_axis),
    )
    return fn(*flat, x)


def dist_ring_blocked_gather_dst_from_src(
    mesh: Mesh, pair: RingBlockedPair, x: jax.Array,
    wire_dtype: Optional[jnp.dtype] = None,
) -> jax.Array:
    """[P*vp, f] vertex-sharded -> aggregated [P*vp, f]; the custom_vjp
    backward runs the REVERSE ring over the transposed step tables
    (gradient push) instead of letting autodiff transpose the forward."""

    @jax.custom_vjp
    def apply(x):
        return _ring_blocked_apply(mesh, pair.fwd, x, wire_dtype)

    def apply_fwd(x):
        return apply(x), None

    def apply_bwd(_, g):
        return (_ring_blocked_apply(mesh, pair.bwd, g, wire_dtype),)

    apply.defvjp(apply_fwd, apply_bwd)
    return apply(x)


def _ring2d_apply(
    mesh: Mesh, rbe: RingBlockedEll, x: jax.Array,
    wire_dtype: Optional[jnp.dtype], pf: int, mode: str = "full",
) -> jax.Array:
    """One direction of the 2D ring: the SAME body as the 1D path, with
    the rotation over the partitioner's vertex axis and ``x``'s feature
    columns sharded ``pf`` ways over the feature axis. A width that does
    not divide ``pf`` is zero-padded to the next multiple around the
    shard_map boundary (shard_map requires even division; the pad
    columns aggregate to zero and are sliced back off) — the body never
    sees a full-width ``[vp, f]`` buffer either way."""
    from neutronstarlite_tpu.parallel.mesh import FEATURE_AXIS, VERTEX_AXIS
    from neutronstarlite_tpu.parallel.partitioner import padded_width

    f = x.shape[1]
    fp = padded_width(f, pf)
    xin = jnp.pad(x, ((0, 0), (0, fp - f))) if fp != f else x
    out = _ring_blocked_apply(
        mesh, rbe, xin, wire_dtype, mode,
        axes=(VERTEX_AXIS, FEATURE_AXIS),
    )
    return out[:, :f] if fp != f else out


def dist_ring2d_gather_dst_from_src(
    mesh: Mesh, pair: RingBlockedPair, x: jax.Array,
    wire_dtype: Optional[jnp.dtype] = None, pf: int = 1,
) -> jax.Array:
    """The 2D-mesh twin of :func:`dist_ring_blocked_gather_dst_from_src`:
    ``[Pv*vp, f]`` (vertex x feature)-sharded -> aggregated, hand-paired
    with the reverse ring over the transposed tables. With ``pf == 1``
    (a ``(Pv, 1)`` mesh) this is bit-for-bit the 1D schedule — the
    partitioner's degenerate layout IS the existing ring."""

    @jax.custom_vjp
    def apply(x):
        return _ring2d_apply(mesh, pair.fwd, x, wire_dtype, pf)

    def apply_fwd(x):
        return apply(x), None

    def apply_bwd(_, g):
        return (_ring2d_apply(mesh, pair.bwd, g, wire_dtype, pf),)

    apply.defvjp(apply_fwd, apply_bwd)
    return apply(x)


def ring_blocked_apply_simulated(
    rbe: RingBlockedEll, x: jax.Array,
    wire_dtype: Optional[jnp.dtype] = None, mode: str = "full",
) -> jax.Array:
    """Collective-free twin: the EXACT step order and f32 carry of the
    shard_map body, with ppermute replaced by explicit shard slicing —
    single-core CI parity (NTS_DIST_SIMULATE / DIST_PATH:ring_blocked_sim).
    ``mode`` mirrors `_ring_blocked_apply` for the overlap probe (here
    the "exchange" is a host-free slice, so probe numbers on the sim rig
    quantify schedule overhead, not real ICI time).
    """
    P, vp = rbe.partitions, rbe.vp
    work = set(rbe.work_steps())
    outs = []
    for p in range(P):
        acc = jnp.zeros((vp, x.shape[1]), jnp.float32)
        last = x[p * vp : (p + 1) * vp]
        for s in range(P):
            if s not in work:
                continue
            q = (
                p if mode == "compute_only"
                else ring_source(p, s, P, rbe.direction)
            )
            shard = x[q * vp : (q + 1) * vp]
            if wire_dtype is not None and s > 0:
                # mirror the collective body exactly: only SHIPPED shards
                # round to the wire dtype; step 0 is the device's own.
                # compute_only keeps the cast too (its "shard" is the
                # resident one, but the probe must measure s>0 table work
                # at the same dtype production runs it)
                shard = shard.astype(wire_dtype)
            last = shard
            if mode == "exchange_only":
                continue
            view = rbe._device_step_view(
                [n[p] for n in rbe.nbr[s]],
                [w[p] for w in rbe.wgt[s]],
                [d[p] for d in rbe.dst_row[s]],
            )
            acc = view.aggregate_into(acc, shard)
        outs.append(
            last.astype(x.dtype) if mode == "exchange_only"
            else acc.astype(x.dtype)
        )
    return jnp.concatenate(outs, axis=0)


def dist_ring_blocked_gather_simulated(
    pair: RingBlockedPair, x: jax.Array,
    wire_dtype: Optional[jnp.dtype] = None,
) -> jax.Array:
    """The sim twin with the SAME hand-paired backward as the collective
    path, so ``jax.grad`` through a sim trainer exercises the reverse-ring
    tables tier-1 tests can reach on one core."""

    @jax.custom_vjp
    def apply(x):
        return ring_blocked_apply_simulated(pair.fwd, x, wire_dtype)

    def apply_fwd(x):
        return apply(x), None

    def apply_bwd(_, g):
        return (ring_blocked_apply_simulated(pair.bwd, g, wire_dtype),)

    apply.defvjp(apply_fwd, apply_bwd)
    return apply(x)


def measure_overlap(
    rbe: RingBlockedEll,
    x: jax.Array,
    mesh: Optional[Mesh] = None,
    wire_dtype: Optional[jnp.dtype] = None,
    repeats: int = 3,
    axes: tuple = (PARTITION_AXIS, None),
) -> dict:
    """Measured ring overlap efficiency: how much of the hop (exchange)
    time hides under the blocked-kernel compute. ``axes`` selects the
    mesh axes exactly as in ``_ring_blocked_apply`` (a 2D-mesh caller
    passes the partitioner's (vertex, feature) pair).

    Times three warm programs over the same input — the production
    overlapped body, its compute-only half (identical table work, no
    hops), and its exchange-only half (the bare dependent hop chain) —
    and reports::

        hidden     = max(compute + exchange - overlapped, 0)
        efficiency = hidden / exchange          (clamped to [0, 1])

    efficiency 1.0 means the ICI transfer is fully hidden (the paper's
    decoupled-overlap ideal, graph.hpp:2644); 0.0 means the schedule
    serializes. On the collective-free sim rig (``mesh=None``) the
    "exchange" is shard slicing, so the number quantifies schedule
    overhead rather than real wire time — still useful as a structural
    regression canary, and the probe record says which rig produced it.

    Three small extra compiles (one per mode) — callers gate it
    (``NTS_OVERLAP_PROBE=1``) rather than paying it on every run.
    """
    import time as _time

    def run_mode(mode: str) -> float:
        if mesh is not None:
            fn = jax.jit(
                lambda a: _ring_blocked_apply(mesh, rbe, a, wire_dtype,
                                              mode=mode, axes=axes)
            )
        else:
            fn = jax.jit(
                lambda a: ring_blocked_apply_simulated(rbe, a, wire_dtype,
                                                       mode=mode)
            )
        jax.block_until_ready(fn(x))  # compile + warm
        ts = []
        for _ in range(max(repeats, 1)):
            t0 = _time.perf_counter()
            jax.block_until_ready(fn(x))
            ts.append(_time.perf_counter() - t0)
        return float(np.median(ts))

    overlap_s = run_mode("full")
    compute_s = run_mode("compute_only")
    exchange_s = run_mode("exchange_only")
    hidden_s = max(compute_s + exchange_s - overlap_s, 0.0)
    efficiency = (
        min(hidden_s / exchange_s, 1.0) if exchange_s > 0 else None
    )
    return {
        "overlap_s": overlap_s,
        "compute_s": compute_s,
        "exchange_s": exchange_s,
        "hidden_s": hidden_s,
        "efficiency": efficiency,
        "simulated": mesh is None,
        "repeats": int(max(repeats, 1)),
    }


def ring_wire_plan(rbe: RingBlockedEll, widths, itemsize: int,
                   pf: int = 1) -> dict:
    """Static per-epoch wire facts for obs/report consumers: one entry per
    rotation hop (the transfer that delivers the shard step s consumes),
    each shipping [vp, slab_width(width, pf)] per layer exchange (the 1D
    mesh is pf=1: the slab IS the full width). ``sum(bytes)`` over the
    plan equals tools/wire_accounting.exchange_rows_per_device *
    sum(slabs) * itemsize when no suffix is skipped; ``slab_cols`` (the
    feature-slab columns each hop carries across all layer exchanges)
    rides every ring_step record so the 2D layout is reconstructable
    from the stream."""
    from neutronstarlite_tpu.parallel.partitioner import slab_width

    slabs = [slab_width(w, pf) for w in widths]
    per_hop = rbe.vp * sum(slabs) * itemsize
    skipped = set(rbe.skipped_steps())
    return {
        "transfers": rbe.n_transfers(),
        "work_steps": rbe.work_steps(),
        "skipped_steps": sorted(skipped),
        "rows_per_transfer": rbe.vp,
        "slab_widths": slabs,
        "slab_cols": sum(slabs),
        "steps": [
            {"step": s, "bytes": per_hop, "skipped": s in skipped,
             "slab_cols": sum(slabs)}
            for s in range(1, rbe.n_transfers() + 1)
        ],
        "peak_resident_rows": min(2, rbe.partitions) * rbe.vp,
        "peak_resident_feature_bytes": (
            min(2, rbe.partitions) * rbe.vp
            * (max(slabs) if slabs else 0) * itemsize
        ),
    }
