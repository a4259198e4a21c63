"""Distributed blocked (source-tiled) ELL: KERNEL_TILE on the dist path.

The dist-ELL exchange (parallel/dist_ell.py) all_gathers the feature
shards and lets each device run a local gather-only aggregation over the
[P*vp, f] gathered array. When that gathered slab outgrows the fast
on-chip gather regime — exactly the situation the single-chip blocked
layout (ops/blocked_ell.py) exists for — each device needs the SOURCE-
TILED local aggregation instead: gathers index only a [vt, f] slice per
scan step, HBM traffic O(E_d * 8 B) table reads + streaming slabs rather
than O(E_d * f) scattered reads. The reference serves its dist engine
with the same tiled CUDA kernels it uses locally
(/root/reference/core/graph.hpp:3640 dispatches ntsCUDAFuseKernel.cuh
unchanged); this module is that composition for the TPU layouts.

Structure: per device, a rectangular BlockedEll (vp destination rows,
P*vp source rows — the round-3 ``src_num`` generalization) built from
the [P, P, Eb] block-grid adjacency; SPMD uniformity then demands one
shape across devices, so per-K levels are stacked [P, T, N_l, K] with
N_l the cross-device max and missing (device, level) pairs padded with
weight-0 rows pointing at the ``vp`` drop sentinel. Inside shard_map
each device slices its tables, rebuilds its BlockedEll view, and runs
the SAME aggregate the single-chip path runs (both scans peel their
first iteration, so the zeros accumulator carry is varying — the
ops/aggregate._scatter_accumulate move; this was the round-2 blocker
that kept KERNEL_TILE single-device, blocked_ell.py's old note).

Backward: custom_vjp pairs the transposed stacked tables (device owns
the src side, neighbors are global dst ids), identical to
dist_ell_gather_dst_from_src — the gradient aggregation is the same
blocked op over the reverse adjacency.

Enable with OPTIM_KERNEL:1 + KERNEL_TILE:vt on a dist trainer (cfg);
COMM_LAYER:ell is implied. NTS_DIST_SIMULATE uses the collective-free
twin below.
"""

from __future__ import annotations

import dataclasses
from typing import List

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as PS

from neutronstarlite_tpu.ops.blocked_ell import BlockedEll
from neutronstarlite_tpu.parallel.dist_ell import per_device_adjacency
from neutronstarlite_tpu.parallel.dist_graph import DistGraph
from neutronstarlite_tpu.parallel.mesh import PARTITION_AXIS, shard_map
from neutronstarlite_tpu.utils.logging import get_logger

log = get_logger("dist_blocked")


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class DistBlockedEll:
    """Stacked per-device rectangular blocked tables.

    Per level l: ``nbr[l]`` [P, T, N_l, K_l] tile-local source ids,
    ``wgt[l]`` [P, T, N_l, K_l], ``dst_row[l]`` [P, T, N_l] device-local
    destination rows (``vp`` on padding rows)."""

    nbr: List[jax.Array]
    wgt: List[jax.Array]
    dst_row: List[jax.Array]
    partitions: int = dataclasses.field(metadata=dict(static=True))
    vp: int = dataclasses.field(metadata=dict(static=True))
    vt: int = dataclasses.field(metadata=dict(static=True))
    n_tiles: int = dataclasses.field(metadata=dict(static=True))

    @staticmethod
    def build(dist: DistGraph, vt: int, transpose: bool = False) -> "DistBlockedEll":
        P, vp = dist.partitions, dist.vp
        per_dev, _ = per_device_adjacency(dist, transpose)
        src_num = P * vp
        n_tiles = -(-src_num // vt)

        # per device: a rectangular single-chip build, keyed by level K
        dev_levels: List[dict] = []
        all_k: set = set()
        for offsets, nbr, w, _deg in per_dev:
            b = BlockedEll.build(vp, offsets, nbr, w, vt, src_num=src_num)
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
                by_k[K][0].shape[1] if K in by_k else 0 for by_k in dev_levels
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

        return DistBlockedEll(
            nbr=nbrs, wgt=wgts, dst_row=dsts,
            partitions=P, vp=vp, vt=int(vt), n_tiles=int(n_tiles),
        )

    def slot_count(self) -> int:
        import math

        return sum(int(math.prod(n.shape)) for n in self.nbr)

    def shard(self, mesh: Mesh) -> "DistBlockedEll":
        from jax.sharding import NamedSharding

        def put(a):
            spec = PS(PARTITION_AXIS, *([None] * (a.ndim - 1)))
            return jax.device_put(a, NamedSharding(mesh, spec))

        return DistBlockedEll(
            nbr=[put(n) for n in self.nbr],
            wgt=[put(w) for w in self.wgt],
            dst_row=[put(d) for d in self.dst_row],
            partitions=self.partitions,
            vp=self.vp, vt=self.vt, n_tiles=self.n_tiles,
        )

    def _device_view(self, nbrs, wgts, dsts) -> BlockedEll:
        """One device's tables (leading P axis already sliced away) as the
        single-chip BlockedEll so the SAME aggregate body runs."""
        return BlockedEll(
            nbr=list(nbrs), wgt=list(wgts), dst_row=list(dsts),
            vt=self.vt, v_num=self.vp, n_tiles=self.n_tiles,
            src_num=self.partitions * self.vp,
        )


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class DistBlockedEllPair:
    """Forward + transposed stacked tables; ``shard(mesh)`` before use."""

    fwd: DistBlockedEll
    bwd: DistBlockedEll

    @staticmethod
    def build(dist: DistGraph, vt: int) -> "DistBlockedEllPair":
        return DistBlockedEllPair(
            fwd=DistBlockedEll.build(dist, vt, transpose=False),
            bwd=DistBlockedEll.build(dist, vt, transpose=True),
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

    def shard(self, mesh: Mesh) -> "DistBlockedEllPair":
        return DistBlockedEllPair(fwd=self.fwd.shard(mesh), bwd=self.bwd.shard(mesh))

    def exchange(self, mesh: Mesh, x: jax.Array, wire_dtype=None,
                 partitioner=None) -> jax.Array:
        return dist_blocked_gather_dst_from_src(mesh, self, x)

    def describe(self) -> str:
        return (
            f"dist blocked aggregation (all_gather + "
            f"[P, {self.fwd.n_tiles}-tile] stacked tables)"
        )


def _dist_blocked_apply(mesh: Mesh, dbl: DistBlockedEll, x: jax.Array) -> jax.Array:
    """all_gather + local blocked aggregation, as a shard_map."""
    n_levels = len(dbl.nbr)

    def body(*args):
        nbrs = [a[0] for a in args[:n_levels]]
        wgts = [a[0] for a in args[n_levels : 2 * n_levels]]
        dsts = [a[0] for a in args[2 * n_levels : 3 * n_levels]]
        xs = args[3 * n_levels]
        xg = lax.all_gather(xs, PARTITION_AXIS, axis=0, tiled=True)  # [P*vp, f]
        return dbl._device_view(nbrs, wgts, dsts).aggregate(xg)

    specs = tuple(
        PS(PARTITION_AXIS, *([None] * (a.ndim - 1)))
        for a in (*dbl.nbr, *dbl.wgt, *dbl.dst_row)
    )
    fn = shard_map(
        body,
        mesh=mesh,
        in_specs=specs + (PS(PARTITION_AXIS, None),),
        out_specs=PS(PARTITION_AXIS, None),
    )
    return fn(*dbl.nbr, *dbl.wgt, *dbl.dst_row, x)


def dist_blocked_gather_dst_from_src(
    mesh: Mesh, pair: DistBlockedEllPair, x: jax.Array
) -> jax.Array:
    """[P*vp, f] vertex-sharded -> aggregated [P*vp, f]; the custom_vjp
    backward runs the transposed stacked tables (gather-only both ways)."""

    @jax.custom_vjp
    def apply(x):
        return _dist_blocked_apply(mesh, pair.fwd, x)

    def apply_fwd(x):
        return apply(x), None

    def apply_bwd(_, g):
        return (_dist_blocked_apply(mesh, pair.bwd, g),)

    apply.defvjp(apply_fwd, apply_bwd)
    return apply(x)


def dist_blocked_gather_simulated(dbl: DistBlockedEll, x: jax.Array) -> jax.Array:
    """Collective-free twin: per-device local aggregation over the full x
    (the all_gather is the identity on a single logical array)."""
    outs = []
    for p in range(dbl.partitions):
        view = dbl._device_view(
            [jnp.asarray(n[p]) for n in dbl.nbr],
            [jnp.asarray(w[p]) for w in dbl.wgt],
            [jnp.asarray(d[p]) for d in dbl.dst_row],
        )
        outs.append(view.aggregate(x))
    return jnp.concatenate(outs, axis=0)
