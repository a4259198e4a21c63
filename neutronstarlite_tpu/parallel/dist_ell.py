"""Distributed gather-only aggregation: all_gather + per-device ELL tables.

The dense ppermute ring (dist_ops.py) interleaves P scatter-add block steps
with P-1 shard rotations — the reference's ring schedule. On TPU the
scatter-add inside each step is the bottleneck (XLA serializes scatter), so
the OPTIM_KERNEL variant of the distributed layer trades the ring for:

  1. one ``all_gather`` of the feature shards over ICI (same total bytes on
     the wire as the full ring rotation: every shard visits every device);
  2. a purely local gather-only ELL aggregation (ops/ell.py) over the
     device's destination rows, with *global* (padded-space) source ids.

SPMD needs identical per-device programs, so the per-device bucket tables
are padded to a common shape: bucket levels K are global (one set of
widths for all devices, chosen by ``ops/ell.level_widths`` to hold the
fewest slots given that), each level's row count is the max over devices,
and padding rows carry weight 0. Each device then consumes its slice of
the stacked ``[P, Nk, K]`` tables — the same "static shapes replace
variable-length messages" move the mirror tables make (parallel/mirror.py).

The backward of (all_gather -> local gather -> inverse-perm) is generated by
autodiff: the transpose of all_gather is reduce-scatter (psum_scatter), and
the transpose of the weighted gather is a scatter-add XLA places on the
*gathered* (pre-reduce) array — for the hand-paired CSR-direction backward
the custom_vjp pairs the transposed tables exactly like ops/ell.EllPair.
"""

from __future__ import annotations

import dataclasses
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as PS

from neutronstarlite_tpu.ops.ell import (
    DEFAULT_SLOT_CHUNK,
    ell_tables_aggregate,
    level_widths,
    table_padding_stats,
)
from neutronstarlite_tpu.parallel.dist_graph import DistGraph
from neutronstarlite_tpu.parallel.mesh import PARTITION_AXIS, shard_map


def per_device_adjacency(dist: DistGraph, transpose: bool):
    """Per-device CSC-style adjacency over GLOBAL padded neighbor ids.

    Returns ([(offsets [vp+1], nbr [e_d], w [e_d], deg [vp])] per device,
    max per-vertex degree across devices). Shared by the stacked dist-ELL
    tables below and the stacked dist-blocked tables
    (parallel/dist_blocked.py)."""
    P, vp = dist.partitions, dist.vp
    per_dev = []
    max_deg = 0
    slot = np.arange(dist.eb)
    for d in range(P):
        own_l = []  # owned-vertex local ids (rows)
        nbr_g = []  # neighbor global padded ids
        ws = []
        for o in range(P):
            # realness comes from the block's explicit edge count (blocks
            # are front-packed), NOT from weight != 0 — a legitimate edge
            # with weight exactly 0 must survive into the tables
            if transpose:
                # device d owns src side: edges in block (p=o, q=d)
                w = dist.block_weight[o, d]
                real = slot < dist.block_count[o, d]
                own_l.append(dist.block_src[o, d][real].astype(np.int64))
                nbr_g.append(dist.block_dst[o, d][real].astype(np.int64) + o * vp)
            else:
                # device d owns dst side: edges in block (p=d, q=o)
                w = dist.block_weight[d, o]
                real = slot < dist.block_count[d, o]
                own_l.append(dist.block_dst[d, o][real].astype(np.int64))
                nbr_g.append(dist.block_src[d, o][real].astype(np.int64) + o * vp)
            ws.append(w[real])
        own = np.concatenate(own_l) if own_l else np.zeros(0, np.int64)
        nbr = np.concatenate(nbr_g) if nbr_g else np.zeros(0, np.int64)
        w = np.concatenate(ws) if ws else np.zeros(0, np.float32)
        order = np.argsort(own, kind="stable")
        own, nbr, w = own[order], nbr[order], w[order]
        deg = np.bincount(own, minlength=vp)
        offsets = np.concatenate([[0], np.cumsum(deg)])
        per_dev.append((offsets, nbr, w, deg))
        if len(deg):
            max_deg = max(max_deg, int(deg.max()))
    return per_dev, max_deg


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class DistEll:
    """Stacked per-device ELL tables in the padded vertex space.

    Per bucket level i: ``nbr[i]`` [P, Nk_i, K_i] global src ids,
    ``wgt[i]`` [P, Nk_i, K_i]; ``inv_perm`` [P, vp] maps each device's local
    dst id to its row in that device's bucket-ordered output (rows beyond
    the real bucket rows are zero and inv_perm points real vertices only at
    real rows).
    """

    nbr: List[jax.Array]
    wgt: List[jax.Array]
    inv_perm: jax.Array
    partitions: int = dataclasses.field(metadata=dict(static=True))
    vp: int = dataclasses.field(metadata=dict(static=True))
    slot_chunk: int = dataclasses.field(metadata=dict(static=True))

    @staticmethod
    def build(dist: DistGraph, slot_chunk: int = DEFAULT_SLOT_CHUNK) -> "DistEll":
        """Forward-direction tables from the [P, P, Eb] blocks: device p's
        rows are its dst vertices, neighbors are global padded src ids."""
        return DistEll._build_from_blocks(dist, transpose=False, slot_chunk=slot_chunk)

    @staticmethod
    def build_transposed(
        dist: DistGraph, slot_chunk: int = DEFAULT_SLOT_CHUNK
    ) -> "DistEll":
        """Transposed tables: device q's rows are its src vertices, neighbors
        are global padded dst ids — the CSR-direction (gradient) aggregation.
        Edge weights are direction-symmetric per edge (gcn_norm/ones), so the
        same per-edge weight serves both directions."""
        return DistEll._build_from_blocks(dist, transpose=True, slot_chunk=slot_chunk)

    @staticmethod
    def _build_from_blocks(dist: DistGraph, transpose: bool, slot_chunk: int):
        P, vp = dist.partitions, dist.vp
        per_dev, _ = per_device_adjacency(dist, transpose)

        # global bucket levels (shared across devices for SPMD uniformity),
        # priced as these stacked tables pay: every level at its fullest
        # device's rows. A zero-degree row (the padded vertex space has
        # them) sits in the first level, so it is priced there.
        levels = level_widths(
            [np.maximum(dev[3], 1) for dev in per_dev]
        ).tolist()

        # per device: assign vertices to levels, then pad rows to the max
        rows_per_level = [0] * len(levels)
        assign = []  # per device: list over levels of vertex-id arrays
        for p in range(P):
            deg = per_dev[p][3]
            order = np.argsort(deg, kind="stable")
            sdeg = deg[order]
            level_ids = []
            i = 0
            for li, K in enumerate(levels):
                j = int(np.searchsorted(sdeg, K, side="right"))
                ids = order[i:j]
                level_ids.append(ids)
                rows_per_level[li] = max(rows_per_level[li], len(ids))
                i = j
            assign.append(level_ids)

        nbrs, wgts = [], []
        inv = np.zeros((P, vp), dtype=np.int32)
        for li, K in enumerate(levels):
            Nk = max(rows_per_level[li], 1)
            nbr = np.zeros((P, Nk, K), dtype=np.int32)
            wgt = np.zeros((P, Nk, K), dtype=np.float32)
            for p in range(P):
                offsets, src, w, deg = per_dev[p]
                ids = assign[p][li]
                if len(ids) == 0:
                    continue
                lo = offsets[ids]
                d = deg[ids]
                k = np.arange(K)
                valid = k[None, :] < d[:, None]
                flat = (lo[:, None] + k[None, :])[valid]
                rows = np.arange(len(ids))
                nbr[p][:len(ids)][valid] = src[flat]
                wgt[p][:len(ids)][valid] = w[flat]
            nbrs.append(nbr)
            wgts.append(wgt)

        # row index of each vertex in the concatenated per-level output
        starts = np.concatenate([[0], np.cumsum([max(r, 1) for r in rows_per_level])])
        for p in range(P):
            for li in range(len(levels)):
                ids = assign[p][li]
                inv[p, ids] = (starts[li] + np.arange(len(ids))).astype(np.int32)

        # host arrays: shard() sends each device its own slice (a jnp array
        # here would land every shard's tables whole on device 0 first)
        return DistEll(
            nbr=nbrs,
            wgt=wgts,
            inv_perm=inv,
            partitions=P,
            vp=vp,
            slot_chunk=int(slot_chunk),
        )

    def slot_count(self) -> int:
        """Total neighbor slots (sum over levels of P * Nk * K) — the padded
        footprint of the tables."""
        import math

        return sum(int(math.prod(n.shape)) for n in self.nbr)

    def shard(self, mesh: Mesh):
        """Device-put the stacked tables sharded over their device axis."""
        from jax.sharding import NamedSharding

        def put(a):
            spec = PS(PARTITION_AXIS, *([None] * (a.ndim - 1)))
            return jax.device_put(a, NamedSharding(mesh, spec))

        return DistEll(
            nbr=[put(n) for n in self.nbr],
            wgt=[put(w) for w in self.wgt],
            inv_perm=put(self.inv_perm),
            partitions=self.partitions,
            vp=self.vp,
            slot_chunk=self.slot_chunk,
        )

    # -- per-device body (collective-free given the gathered x) ------------
    def _local_aggregate(self, p_tables, xg: jax.Array) -> jax.Array:
        """p_tables: ([Nk, K] nbr, [Nk, K] wgt) per level + [vp] inv row map;
        xg [P*vp, f] fully gathered features."""
        nbrs, wgts, inv = p_tables
        # shared level-reduction core (single source of the f32-accumulation
        # numeric policy across single-device and distributed paths)
        return ell_tables_aggregate(xg, nbrs, wgts, self.slot_chunk)[inv]


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class DistEllPair:
    """Forward + transposed tables; ``shard(mesh)`` before use."""

    fwd: DistEll
    bwd: DistEll

    @staticmethod
    def build(
        dist: DistGraph, slot_chunk: int = DEFAULT_SLOT_CHUNK
    ) -> "DistEllPair":
        return DistEllPair(
            fwd=DistEll.build(dist, slot_chunk),
            bwd=DistEll.build_transposed(dist, slot_chunk),
        )

    def padding_stats(self, real_edges: int) -> dict:
        """Slot occupancy of both directions (VERDICT round-1 item 8: the
        padded-layout waste must be quantified and bounded, the analog of the
        reference's balanced-chunk goal, core/graph.hpp:1186-1211).

        Sources of padding: a degree's rounding up to its level's width and
        the cross-device row max per level (SPMD-uniform shapes);
        ``ops/ell.level_widths`` chooses the widths that make their sum
        least."""
        return table_padding_stats(self.fwd.nbr, self.bwd.nbr, real_edges)

    def shard(self, mesh: Mesh) -> "DistEllPair":
        return DistEllPair(fwd=self.fwd.shard(mesh), bwd=self.bwd.shard(mesh))

    def exchange(self, mesh: Mesh, x: jax.Array, wire_dtype=None,
                 partitioner=None) -> jax.Array:
        return dist_ell_gather_dst_from_src(mesh, self, x)

    def describe(self) -> str:
        return (
            f"dist gather-only aggregation (all_gather + "
            f"{len(self.fwd.nbr)}-level ELL tables)"
        )


def _dist_ell_apply(mesh: Mesh, dell: DistEll, x: jax.Array) -> jax.Array:
    """all_gather + local ELL aggregation, as a shard_map."""

    def body(*args):
        *tables, xs = args
        n_levels = len(dell.nbr)
        nbrs = [t[0] for t in tables[:n_levels]]
        wgts = [t[0] for t in tables[n_levels : 2 * n_levels]]
        inv = tables[2 * n_levels][0]
        xg = lax.all_gather(xs, PARTITION_AXIS, axis=0, tiled=True)  # [P*vp, f]
        return dell._local_aggregate((nbrs, wgts, inv), xg)

    table_specs = tuple(
        PS(PARTITION_AXIS, None, None) for _ in range(2 * len(dell.nbr))
    ) + (PS(PARTITION_AXIS, None),)
    fn = shard_map(
        body,
        mesh=mesh,
        in_specs=table_specs + (PS(PARTITION_AXIS, None),),
        out_specs=PS(PARTITION_AXIS, None),
    )
    return fn(*dell.nbr, *dell.wgt, dell.inv_perm, x)


def dist_ell_gather_dst_from_src(
    mesh: Mesh, pair: DistEllPair, x: jax.Array
) -> jax.Array:
    """[P*vp, f] vertex-sharded -> aggregated [P*vp, f], gather-only in BOTH
    directions: the custom_vjp backward runs the transposed tables instead of
    letting autodiff transpose the gather into a scatter-add."""

    @jax.custom_vjp
    def apply(x):
        return _dist_ell_apply(mesh, pair.fwd, x)

    def apply_fwd(x):
        return apply(x), None

    def apply_bwd(_, g):
        return (_dist_ell_apply(mesh, pair.bwd, g),)

    apply.defvjp(apply_fwd, apply_bwd)
    return apply(x)


def dist_ell_gather_simulated(dell: DistEll, x: jax.Array) -> jax.Array:
    """Collective-free twin: per-device local aggregation over the full x
    (the all_gather is the identity on a single logical array)."""
    P, vp = dell.partitions, dell.vp
    outs = []
    for p in range(P):
        tables = (
            [jnp.asarray(n[p]) for n in dell.nbr],
            [jnp.asarray(w[p]) for w in dell.wgt],
            jnp.asarray(dell.inv_perm[p]),
        )
        outs.append(dell._local_aggregate(tables, x))
    return jnp.concatenate(outs, axis=0)
