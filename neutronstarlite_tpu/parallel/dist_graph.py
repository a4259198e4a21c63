"""Vertex-sharded distributed graph: per-(dst,src)-partition edge blocks.

The TPU re-design of the reference's partitioned storage + mirror machinery:

- Vertices are range-partitioned with the alpha-weighted edge-balancing
  chunker (graph.hpp:1186-1211 — see graph.storage.partition_offsets), each
  range padded to the max range size ``vp`` so every shard has a static shape
  (XLA needs static shapes where the reference used variable-length MPI
  messages — SURVEY.md "hard parts").
- For each (dst partition p, src partition q) the edges are an independent
  CSC-sorted block — exactly the reference's per-source-partition
  CSC_segment_pinned chunks (GraphSegment.h:52, PartitionedGraph.hpp:324-420
  PartitionToChunks). Blocks are padded to a common length and stacked into
  [P, P, Eb] arrays sharded over the dst axis, so device p holds its own row
  of chunks in HBM.
- The master/mirror distinction dissolves: a "mirror" is just a row of the
  remote shard that arrives during the ring exchange (dist_ops.py); no
  MirrorIndex tables are materialized because the ring ships whole padded
  shards whose shapes are known at trace time. (A compacted mirror-slot
  variant is the DepCache-style optimization — see SURVEY.md section 2.9.9.)

Local vertex ids: vertex v owned by partition p maps to padded global id
``p * vp + (v - offsets[p])``. Feature/label/mask arrays are re-laid-out into
the padded [P * vp, ...] space with ``pad_vertex_array``.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import numpy as np

from neutronstarlite_tpu.graph.storage import CSCGraph, partition_offsets
from neutronstarlite_tpu.parallel.mesh import shard_leading
from neutronstarlite_tpu.parallel.vertex_space import (
    PaddedVertexSpace,
    owner_of_vertices,
    round_up,
)

_round_up = round_up  # layout helper shared with MirrorGraph


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class RingBlocks:
    """Step-major ring edge blocks: per ring step s, [P, Eb_s] arrays whose
    row p is edge block (p, (p+s) % P) — see DistGraph.step_blocks."""

    src: list
    dst: list
    wgt: list
    vp: int = dataclasses.field(metadata=dict(static=True))
    edge_chunk: int = dataclasses.field(metadata=dict(static=True))

    def shard(self, mesh) -> "RingBlocks":
        """Device-put every step's arrays sharded over devices."""
        return jax.tree.map(lambda a: shard_leading(mesh, a), self)

    def exchange(self, mesh, x: jax.Array, wire_dtype=None,
                 partitioner=None) -> jax.Array:
        from neutronstarlite_tpu.parallel.dist_ops import (
            dist_gather_dst_from_src,
        )

        return dist_gather_dst_from_src(mesh, self, x)

    def describe(self) -> str:
        return f"ppermute ring ({len(self.src)} steps, vp={self.vp})"


@dataclasses.dataclass
class DistGraph(PaddedVertexSpace):
    """Host-side container; ``device_blocks()`` ships the block arrays."""

    partitions: int
    vp: int  # padded vertices per partition (static)
    offsets: np.ndarray  # [P+1] original-id partition boundaries
    # [P, P, Eb] block arrays, CSC (dst-sorted) order inside each block:
    # block[p, q] holds edges with dst in partition p, src in partition q;
    # indices are partition-local (src - offsets[q], dst - offsets[p]).
    block_src: np.ndarray
    block_dst: np.ndarray
    block_weight: np.ndarray
    e_num: int
    v_num: int
    edge_chunk: int
    # [P, P] real (unpadded) edge count per block — the authoritative
    # realness source for derived layouts (a weight-0 edge is still an edge)
    block_count: np.ndarray = None

    @property
    def eb(self) -> int:
        return self.block_src.shape[2]

    @staticmethod
    def build(
        g: CSCGraph,
        partitions: int,
        edge_chunk: Optional[int] = None,
        lane_pad: int = 8,
    ) -> "DistGraph":
        """Partition a host graph into the [P, P, Eb] block layout.

        (GenerateAll's role: generatePartitionedSubgraph -> PartitionToChunks,
        PartitionedGraph.hpp:80.)"""
        P = partitions
        offsets = partition_offsets(g.v_num, g.in_degree, P)
        sizes = np.diff(offsets)
        vp = _round_up(int(sizes.max()), lane_pad)

        # owner partition of each vertex id
        owner = owner_of_vertices(offsets)

        src = g.row_indices.astype(np.int64)  # CSC order: dst-sorted
        dst = g.dst_of_edge.astype(np.int64)
        w = g.edge_weight_forward
        p_of_edge = owner[dst]
        q_of_edge = owner[src]

        # group edges by (p, q); CSC order is preserved inside each group
        # because the grouping sort is stable.
        key = p_of_edge * P + q_of_edge
        order = np.argsort(key, kind="stable")
        src_s, dst_s, w_s, key_s = src[order], dst[order], w[order], key[order]
        counts = np.bincount(key_s, minlength=P * P)
        eb = _round_up(int(counts.max()) if counts.size else 1, 8)
        if edge_chunk is None:
            from neutronstarlite_tpu.ops.device_graph import DEFAULT_EDGE_CHUNK

            edge_chunk = min(DEFAULT_EDGE_CHUNK, max(128, eb))
        eb = _round_up(eb, edge_chunk)

        block_src = np.zeros((P, P, eb), dtype=np.int32)
        block_dst = np.zeros((P, P, eb), dtype=np.int32)
        block_weight = np.zeros((P, P, eb), dtype=np.float32)
        starts = np.concatenate([[0], np.cumsum(counts)])
        for p in range(P):
            for q in range(P):
                k = p * P + q
                lo, hi = starts[k], starts[k + 1]
                n = hi - lo
                if n == 0:
                    continue
                block_src[p, q, :n] = src_s[lo:hi] - offsets[q]
                block_dst[p, q, :n] = dst_s[lo:hi] - offsets[p]
                block_weight[p, q, :n] = w_s[lo:hi]

        return DistGraph(
            partitions=P,
            vp=vp,
            offsets=offsets,
            block_src=block_src,
            block_dst=block_dst,
            block_weight=block_weight,
            e_num=g.e_num,
            v_num=g.v_num,
            edge_chunk=int(edge_chunk),
            block_count=counts.reshape(P, P).astype(np.int64),
        )

    def padding_stats(self) -> dict:
        """Padded-vs-real occupancy of the [P, P, Eb] layout — the scaling
        liability to watch on power-law graphs (every block pads to the
        global max; the reference instead balances chunks explicitly,
        core/graph.hpp:1186-1211). DistGCNTrainer logs this at build."""
        real = int(self.block_count.sum())
        padded = int(self.block_src.size)
        return {
            "real_edges": real,
            "padded_edges": padded,
            "waste_ratio": padded / max(real, 1),
            "max_block": int(self.block_count.max()),
            "mean_block": float(self.block_count.mean()),
        }

    def step_blocks(self) -> "RingBlocks":
        """Re-pack the [P, P, Eb] blocks into the ring's STEP-MAJOR device
        layout: per ring step s, a [P, Eb_s] triple whose row p is block
        (p, (p+s) % P), padded only to that step's cross-device max (and
        the edge_chunk multiple the chunked scatter needs).

        This is the round-3 padding bound (VERDICT round-2 item 6): the
        uniform layout pads every block to the GLOBAL max — on a power-law
        graph the dominant diagonal (local) blocks set Eb and every remote
        block pays it. Per-step padding is the TPU-legal version of the
        reference's per-chunk exact sizes (core/graph.hpp:1186-1211):
        shapes stay static and identical across devices (SPMD), but each
        step only pays its own diagonal's max. Bonus: the per-device body
        indexes its row directly — no dynamic_index_in_dim over q."""
        P = self.partitions
        src_l, dst_l, w_l = [], [], []
        for s, eb_s in enumerate(self._step_sizes()):
            bs = np.zeros((P, eb_s), dtype=np.int32)
            bd = np.zeros((P, eb_s), dtype=np.int32)
            bw = np.zeros((P, eb_s), dtype=np.float32)
            for p in range(P):
                q = (p + s) % P
                n = int(self.block_count[p, q])
                bs[p, :n] = self.block_src[p, q, :n]
                bd[p, :n] = self.block_dst[p, q, :n]
                bw[p, :n] = self.block_weight[p, q, :n]
            # host numpy: the single device transfer happens in shard()
            # with the right layout (a jnp.asarray here would land every
            # step's bytes on device 0 first, then copy again)
            src_l.append(bs)
            dst_l.append(bd)
            w_l.append(bw)
        return RingBlocks(
            src=src_l, dst=dst_l, wgt=w_l, vp=self.vp,
            edge_chunk=self.edge_chunk,
        )

    def _step_sizes(self) -> list:
        """Per-ring-step padded block length Eb_s — the ONE source of the
        step-major sizing rule (step_blocks and step_padding_stats share it
        so the stats can never diverge from what the ring ships)."""
        P = self.partitions
        return [
            _round_up(
                max(
                    max(int(self.block_count[p, (p + s) % P]) for p in range(P)),
                    1,
                ),
                self.edge_chunk,
            )
            for s in range(P)
        ]

    def step_padding_stats(self) -> dict:
        """Occupancy of the step-major layout (what the ring actually
        ships to HBM), next to the uniform [P, P, Eb] layout's."""
        padded = self.partitions * sum(self._step_sizes())
        real = int(self.block_count.sum())
        return {
            "real_edges": real,
            "padded_edges": padded,
            "waste_ratio": padded / max(real, 1),
        }

    def shard(self, mesh) -> "RingBlocks":
        """Device-put the step-major ring blocks sharded over devices."""
        return self.step_blocks().shard(mesh)
