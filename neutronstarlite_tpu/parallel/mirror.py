"""Mirror-index graph: fixed-capacity mirror slots + local edge lists.

The TPU re-design of the reference's mirror machinery
(PartitionedGraph::generateMirrorIndex, PartitionedGraph.hpp:295-305, the
prefix-sum ``MirrorIndex`` / ``owned_mirrors`` tables) and of the compacted
master->mirror messages the MPI ring ships (only *active* sources travel,
network.cpp:505-518). XLA needs static shapes, so the variable-length message
sets become **fixed-capacity mirror slots** precomputed at preprocessing time
(SURVEY.md section 7 "hard parts": "fixed-capacity mirror slots precomputed
from MirrorIndex (preferred; shapes known at trace time)"):

- For each (consumer partition p, producer partition q) the set of q-owned
  vertices referenced as a source by p's in-edges is deduplicated and padded
  to a common capacity ``Mb``. ``need_ids[q, p]`` holds those q-local ids —
  sharded over q, it is the gather table each producer device applies to its
  feature shard before the one-shot ``all_to_all`` exchange
  (dist_edge_ops.dist_get_dep_nbr, the DistGetDepNbrOp equivalent).
- Each device p's in-edges are merged across q into ONE dst-sorted local edge
  list (the role of GenerateWholeGraphTopo's local CSC over masters +
  compressed CSR over mirrors, PartitionedGraph.hpp:105-143): ``edge_dst`` is
  p-local, ``edge_src_slot`` indexes the [P*Mb] mirror space ``q*Mb + slot``.
  Dst-sortedness lets every downstream edge op use sorted segment reductions.

Comm volume per device per layer is P*Mb rows instead of the P*vp rows the
dense ppermute ring ships (dist_ops.py) — the same saving the reference gets
from sending only active mirrors instead of whole partitions.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import numpy as np

from neutronstarlite_tpu.graph.storage import CSCGraph, partition_offsets
from neutronstarlite_tpu.parallel.mesh import shard_leading
from neutronstarlite_tpu.parallel.vertex_space import PaddedVertexSpace, round_up


def shard_tables(mesh, arrays) -> Tuple[jax.Array, ...]:
    """Device-put each array sharded over its leading (partition) axis."""
    return tuple(shard_leading(mesh, a) for a in arrays)


def build_local_edge_lists(P, vp, offsets, p_of_edge, slot_global, dst, w):
    """Pass 2 shared by MirrorGraph and CachedMirrorGraph: per-consumer
    dst-sorted edge lists in mirror-slot coordinates (stable grouping by p
    preserves the global CSC dst order per group)."""
    p_counts = np.bincount(p_of_edge, minlength=P)
    el = round_up(max(int(p_counts.max()), 1), 8)
    order = np.argsort(p_of_edge, kind="stable")
    p_starts = np.concatenate([[0], np.cumsum(p_counts)])
    edge_src_slot = np.zeros((P, el), dtype=np.int32)
    edge_dst = np.full((P, el), vp - 1, dtype=np.int32)  # keep sorted tail
    edge_weight = np.zeros((P, el), dtype=np.float32)
    edge_mask = np.zeros((P, el), dtype=np.float32)
    for p in range(P):
        sel = order[p_starts[p] : p_starts[p + 1]]
        n = len(sel)
        if n == 0:
            continue
        edge_src_slot[p, :n] = slot_global[sel].astype(np.int32)
        edge_dst[p, :n] = (dst[sel] - offsets[p]).astype(np.int32)
        edge_weight[p, :n] = w[sel]
        edge_mask[p, :n] = 1.0
    return edge_src_slot, edge_dst, edge_weight, edge_mask


@dataclasses.dataclass
class MirrorGraph(PaddedVertexSpace):
    """Host-side mirror-slot tables; ``shard()`` ships them to the mesh."""

    partitions: int
    vp: int  # padded vertices per partition (static)
    mb: int  # mirror slots per (p, q) pair (static)
    offsets: np.ndarray  # [P+1] original-id partition boundaries
    # [P(q), P(p), Mb] q-local vertex ids that consumer p needs from producer q
    need_ids: np.ndarray
    # [P, El] per-consumer local edge list, dst-sorted:
    edge_src_slot: np.ndarray  # int32 into the [P*Mb] mirror space
    edge_dst: np.ndarray  # int32 p-local dst
    edge_weight: np.ndarray  # float32, 0 on padding
    edge_mask: np.ndarray  # float32 {0, 1}
    e_num: int
    v_num: int

    @property
    def el(self) -> int:
        return self.edge_dst.shape[1]

    @staticmethod
    def estimate_mb(g: CSCGraph, partitions: int, lane_pad: int = 8):
        """(mb, vp) without building the tables — pass 1 only (the
        unique-pair count). Lets COMM_LAYER:auto price the mirror exchange
        cheaply before committing to a layout."""
        P = partitions
        offsets = partition_offsets(g.v_num, g.in_degree, P)
        vp = round_up(max(int(np.diff(offsets).max()), 1), lane_pad)
        owner = np.searchsorted(offsets, np.arange(g.v_num), side="right") - 1
        src = g.row_indices.astype(np.int64)
        dst = g.dst_of_edge.astype(np.int64)
        key_pq = owner[dst] * P + owner[src]
        u = np.unique(key_pq * g.v_num + src)
        pq_counts = np.bincount(u // g.v_num, minlength=P * P)
        mb = round_up(max(int(pq_counts.max()) if pq_counts.size else 1, 1), lane_pad)
        return mb, vp

    @staticmethod
    def build(g: CSCGraph, partitions: int, lane_pad: int = 8) -> "MirrorGraph":
        P = partitions
        offsets = partition_offsets(g.v_num, g.in_degree, P)
        sizes = np.diff(offsets)
        vp = round_up(max(int(sizes.max()), 1), lane_pad)

        owner = np.searchsorted(offsets, np.arange(g.v_num), side="right") - 1
        src = g.row_indices.astype(np.int64)  # global CSC order: dst-sorted
        dst = g.dst_of_edge.astype(np.int64)
        w = g.edge_weight_forward.astype(np.float32)
        p_of_edge = owner[dst]
        q_of_edge = owner[src]

        # pass 1: per-(p, q) deduplicated source sets -> capacity Mb. One
        # sorted-unique over the composite key (p, q, src) replaces a P*P
        # full-array scan: (p*P + q)*V + src sorts by pair then source, so
        # each pair's unique sources are a contiguous sorted run.
        key_pq = p_of_edge * P + q_of_edge
        pair = key_pq * g.v_num + src
        u = np.unique(pair)
        u_pq = u // g.v_num
        pq_counts = np.bincount(u_pq, minlength=P * P)
        mb = round_up(max(int(pq_counts.max()) if pq_counts.size else 1, 1), lane_pad)
        u_starts = np.concatenate([[0], np.cumsum(pq_counts)])
        u_src_local = (u % g.v_num) - offsets[u_pq % P]

        need_ids = np.zeros((P, P, mb), dtype=np.int32)
        for k in np.nonzero(pq_counts)[0]:
            p, q = divmod(int(k), P)
            lo, hi = u_starts[k], u_starts[k + 1]
            need_ids[q, p, : hi - lo] = u_src_local[lo:hi].astype(np.int32)

        # every edge's slot = its position inside its pair's unique run
        slot_in_pair = np.searchsorted(u, pair) - u_starts[key_pq]
        slot_global = q_of_edge * mb + slot_in_pair

        edge_src_slot, edge_dst, edge_weight, edge_mask = build_local_edge_lists(
            P, vp, offsets, p_of_edge, slot_global, dst, w
        )

        return MirrorGraph(
            partitions=P,
            vp=vp,
            mb=mb,
            offsets=offsets,
            need_ids=need_ids,
            edge_src_slot=edge_src_slot,
            edge_dst=edge_dst,
            edge_weight=edge_weight,
            edge_mask=edge_mask,
            e_num=g.e_num,
            v_num=g.v_num,
        )

    def shard(self, mesh) -> Tuple[jax.Array, ...]:
        """Device-put (need_ids, edge_src_slot, edge_dst, edge_weight,
        edge_mask) sharded over their leading partition axis."""
        return shard_tables(mesh, (
            self.need_ids, self.edge_src_slot, self.edge_dst,
            self.edge_weight, self.edge_mask,
        ))


@dataclasses.dataclass
class ChunkedEdgeList:
    """Dst-ALIGNED chunking of a MirrorGraph's per-device edge list.

    Why (round 5): the GGCN dist chain materializes f'-wide edge tensors;
    at full Reddit (El=14.6M, f'=128) the un-chunked chain needs ~77 GiB
    of HBM temp (AOT-measured, docs/perf_runs/round5/) against a 15.75 GiB
    chip. Cutting the dst-sorted edge list at DST boundaries keeps every
    per-dst softmax segment whole inside one chunk, so the chain runs
    chunk-at-a-time (live edge tensors ~Ec*f') with per-chunk
    rematerialization, and per-chunk outputs cover contiguous dst ranges
    placed by the same ordered dynamic_update_slice invariant the
    segmented dist-bsp uses. Reference analog: the El-blocked structure
    SURVEY §7 anticipates for the GAT_CPU_DIST chain (:185-211).

    Shapes (uniform over devices and chunks; pad chunks have mask 0 and
    base == vp, the scratch row):
      slot  [P, n_ch, Ec]  int32 into the [P*Mb] mirror space
      dstl  [P, n_ch, Ec]  int32 p-LOCAL dst (for gathering dst-side rows)
      dstr  [P, n_ch, Ec]  int32 chunk-RELATIVE dst (for softmax/segsum)
      mask  [P, n_ch, Ec]  f32 {0, 1}
      base  [P, n_ch]      int32 first dst row of the chunk
      dp    static: padded dst rows per chunk
    """

    slot: np.ndarray
    dstl: np.ndarray
    dstr: np.ndarray
    mask: np.ndarray
    base: np.ndarray
    dp: int

    def shard(self, mesh):
        return shard_tables(
            mesh, (self.slot, self.dstl, self.dstr, self.mask, self.base)
        )


def chunk_edge_list(mg: "MirrorGraph", ec_target: int) -> ChunkedEdgeList:
    """Cut each device's dst-sorted edge list into dst-aligned chunks of at
    most max(ec_target, heaviest dst) edges."""
    P, vp = mg.partitions, mg.vp
    per_dev = []
    max_ec = max_dp = max_nch = 1
    for p in range(P):
        m = mg.edge_mask[p] > 0
        d = mg.edge_dst[p][m]
        s = mg.edge_src_slot[p][m]
        counts = np.bincount(d, minlength=vp)
        nz = np.nonzero(counts)[0]
        ec = max(int(ec_target), int(counts.max()) if nz.size else 1)
        chunks = []  # (edge_lo, edge_hi, dst_lo, dst_hi)
        e_lo, d_lo, acc = 0, 0, 0
        prev_hi = 0
        for v in nz:
            c = int(counts[v])
            if acc and acc + c > ec:
                chunks.append((e_lo, e_lo + acc, d_lo, prev_hi + 1))
                e_lo += acc
                d_lo = int(v)
                acc = 0
            acc += c
            prev_hi = int(v)
        chunks.append((e_lo, e_lo + acc, d_lo, prev_hi + 1 if nz.size else 1))
        per_dev.append((d, s, chunks))
        max_ec = max(max_ec, max(h - l for l, h, *_ in chunks))
        max_dp = max(max_dp, max(dh - dl for *_, dl, dh in chunks))
        max_nch = max(max_nch, len(chunks))
    Ec = round_up(max_ec, 8)
    dp = round_up(max_dp, 8)
    n_ch = max_nch

    slot = np.zeros((P, n_ch, Ec), np.int32)
    dstl = np.full((P, n_ch, Ec), vp - 1, np.int32)
    dstr = np.full((P, n_ch, Ec), dp - 1, np.int32)  # sorted pad tail
    mask = np.zeros((P, n_ch, Ec), np.float32)
    base = np.full((P, n_ch), vp, np.int32)  # pad chunks -> scratch margin
    for p, (d, s, chunks) in enumerate(per_dev):
        for k, (el, eh, dl, dh) in enumerate(chunks):
            n = eh - el
            if n == 0:
                continue
            slot[p, k, :n] = s[el:eh]
            dstl[p, k, :n] = d[el:eh]
            dstr[p, k, :n] = d[el:eh] - dl
            mask[p, k, :n] = 1.0
            base[p, k] = dl
    return ChunkedEdgeList(slot=slot, dstl=dstl, dstr=dstr, mask=mask,
                           base=base, dp=int(dp))


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class SplitMirrorTables:
    """A SplitMirror's nine tables on the mesh (``SplitMirror.shard``),
    each sharded over its leading partition axis, with the sizes the
    exchange reads."""

    need_ids: jax.Array
    r_src_slot: jax.Array
    r_dst: jax.Array
    r_weight: jax.Array
    r_mask: jax.Array
    l_src: jax.Array
    l_dst: jax.Array
    l_weight: jax.Array
    l_mask: jax.Array
    partitions: int = dataclasses.field(metadata=dict(static=True))
    vp: int = dataclasses.field(metadata=dict(static=True))
    mb: int = dataclasses.field(metadata=dict(static=True))

    def shard(self, mesh) -> "SplitMirrorTables":
        """Device-put all 9 tables sharded over their leading axis."""
        return jax.tree.map(lambda a: shard_leading(mesh, a), self)

    def exchange(self, mesh, x: jax.Array, wire_dtype=None,
                 partitioner=None) -> jax.Array:
        from neutronstarlite_tpu.parallel.dist_edge_ops import (
            dist_gather_dst_from_src_mirror_split,
        )

        return dist_gather_dst_from_src_mirror_split(mesh, self, self, x)

    def describe(self) -> str:
        return (
            f"split mirror: remote-only all_to_all (mb={self.mb} remote "
            f"slots/pair vs vp={self.vp} shard rows; "
            f"Er={self.r_dst.shape[1]} remote + El={self.l_dst.shape[1]} "
            f"resident edges)"
        )


@dataclasses.dataclass
class SplitMirror(PaddedVertexSpace):
    """Remote-only mirror exchange + resident local edge list (round 5).

    On any graph WITH SELF-LOOPS (every GCN ``.edge.self`` input) the
    diagonal (p, p) need-set of the uniform MirrorGraph layout saturates at
    vp BY CONSTRUCTION — each vertex is its own source — so all P*P pairs
    pad to Mb == vp and the "compacted" exchange degenerates to the dense
    ring's volume. But diagonal rows are already RESIDENT on their consumer:
    here they never enter the exchange at all. ``mb`` is the max
    OFF-DIAGONAL need, the exchanged tensor is [P, P*mb, f], and local-src
    edges carry p-local source ids read directly from the feature shard.
    Aggregation = segment-sum over the remote edge list (mirror slots) +
    segment-sum over the local edge list (shard rows).

    Reference analog: the active-mirror compaction (network.cpp:505-518,
    PartitionedGraph.hpp:174-285) — whose MPI form also never ships a
    master to itself.

    Additive: the GCN-family fused aggregation consumes this; the GAT/GGCN
    edge-op chain and the DepCache keep the uniform MirrorGraph layout."""

    partitions: int
    vp: int
    mb: int  # REMOTE mirror slots per (p, q!=p) pair
    offsets: np.ndarray
    need_ids: np.ndarray  # [P(q), P(p), mb]; diagonal rows dead (zeros)
    r_src_slot: np.ndarray  # [P, Er] int32 into the [P*mb] mirror space
    r_dst: np.ndarray  # [P, Er] int32 p-local dst
    r_weight: np.ndarray  # [P, Er] f32 (0 on padding)
    r_mask: np.ndarray  # [P, Er] f32 {0, 1}
    l_src: np.ndarray  # [P, El] int32 p-LOCAL src vertex id
    l_dst: np.ndarray  # [P, El] int32 p-local dst
    l_weight: np.ndarray  # [P, El] f32 (0 on padding)
    l_mask: np.ndarray  # [P, El] f32 {0, 1}
    e_num: int
    v_num: int

    @property
    def er(self) -> int:
        return self.r_dst.shape[1]

    @property
    def el(self) -> int:
        return self.l_dst.shape[1]

    @staticmethod
    def estimate_mb_remote(g: CSCGraph, partitions: int, lane_pad: int = 8):
        """(mb_remote, vp) without building tables — the wire price of the
        split exchange for COMM_LAYER:auto."""
        P = partitions
        offsets = partition_offsets(g.v_num, g.in_degree, P)
        vp = round_up(max(int(np.diff(offsets).max()), 1), lane_pad)
        owner = np.searchsorted(offsets, np.arange(g.v_num), side="right") - 1
        src = g.row_indices.astype(np.int64)
        dst = g.dst_of_edge.astype(np.int64)
        p_of_edge = owner[dst]
        q_of_edge = owner[src]
        remote = p_of_edge != q_of_edge
        key_pq = p_of_edge[remote] * P + q_of_edge[remote]
        u = np.unique(key_pq * g.v_num + src[remote])
        pq_counts = np.bincount(u // g.v_num, minlength=P * P)
        mb = round_up(max(int(pq_counts.max()) if pq_counts.size else 1, 1),
                      lane_pad)
        return mb, vp

    @staticmethod
    def build(g: CSCGraph, partitions: int, lane_pad: int = 8) -> "SplitMirror":
        P = partitions
        offsets = partition_offsets(g.v_num, g.in_degree, P)
        sizes = np.diff(offsets)
        vp = round_up(max(int(sizes.max()), 1), lane_pad)

        owner = np.searchsorted(offsets, np.arange(g.v_num), side="right") - 1
        src = g.row_indices.astype(np.int64)  # global CSC order: dst-sorted
        dst = g.dst_of_edge.astype(np.int64)
        w = g.edge_weight_forward.astype(np.float32)
        p_of_edge = owner[dst]
        q_of_edge = owner[src]
        remote = p_of_edge != q_of_edge

        # pass 1 over REMOTE edges only: per-(p, q!=p) deduplicated source
        # sets -> capacity mb (same sorted-unique trick as MirrorGraph)
        key_pq_r = p_of_edge[remote] * P + q_of_edge[remote]
        pair_r = key_pq_r * g.v_num + src[remote]
        u = np.unique(pair_r)
        u_pq = u // g.v_num
        pq_counts = np.bincount(u_pq, minlength=P * P)
        mb = round_up(max(int(pq_counts.max()) if pq_counts.size else 1, 1),
                      lane_pad)
        u_starts = np.concatenate([[0], np.cumsum(pq_counts)])
        u_src_local = (u % g.v_num) - offsets[u_pq % P]

        need_ids = np.zeros((P, P, mb), dtype=np.int32)
        for k in np.nonzero(pq_counts)[0]:
            p, q = divmod(int(k), P)
            need_ids[q, p, : u_starts[k + 1] - u_starts[k]] = u_src_local[
                u_starts[k] : u_starts[k + 1]
            ].astype(np.int32)

        slot_in_pair = np.searchsorted(u, pair_r) - u_starts[key_pq_r]
        slot_global = q_of_edge[remote] * mb + slot_in_pair
        r_src_slot, r_dst, r_weight, r_mask = build_local_edge_lists(
            P, vp, offsets, p_of_edge[remote], slot_global,
            dst[remote], w[remote],
        )

        # local edges keep p-local SOURCE ids (read from the shard)
        local = ~remote
        src_local = src[local] - offsets[p_of_edge[local]]
        l_src, l_dst, l_weight, l_mask = build_local_edge_lists(
            P, vp, offsets, p_of_edge[local], src_local,
            dst[local], w[local],
        )

        return SplitMirror(
            partitions=P, vp=vp, mb=mb, offsets=offsets, need_ids=need_ids,
            r_src_slot=r_src_slot, r_dst=r_dst, r_weight=r_weight,
            r_mask=r_mask, l_src=l_src, l_dst=l_dst, l_weight=l_weight,
            l_mask=l_mask, e_num=g.e_num, v_num=g.v_num,
        )

    def tables(self) -> SplitMirrorTables:
        """The nine tables on the host, as the exchange takes them."""
        return SplitMirrorTables(
            need_ids=self.need_ids, r_src_slot=self.r_src_slot,
            r_dst=self.r_dst, r_weight=self.r_weight, r_mask=self.r_mask,
            l_src=self.l_src, l_dst=self.l_dst, l_weight=self.l_weight,
            l_mask=self.l_mask, partitions=self.partitions, vp=self.vp,
            mb=self.mb,
        )

    def shard(self, mesh) -> SplitMirrorTables:
        return self.tables().shard(mesh)
