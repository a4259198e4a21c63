"""Blocked (source-tiled) ELL aggregation: the beyond-VMEM hot path.

The plain ELL layout (ops/ell.py) wins on TPU because XLA serves its random
row gathers from on-chip memory — measured at multi-TB/s when the gathered
table fits VMEM (docs/PERF.md section 1). Past that size every gathered row
is an HBM transaction and the op costs O(E * f) HBM bytes per application
(e.g. Reddit's standard-order first layer: [233k, 602] bf16 = 280 MB table,
~69 GB of gather traffic per epoch).

This module tiles the SOURCE dimension instead: vertices are cut into T
contiguous tiles of ``vt`` rows; each tile owns the sub-adjacency of edges
whose source lies in the tile, with tile-LOCAL source ids, so every gather
indexes only a [vt, f] slice sized to the on-chip budget. HBM traffic
becomes O(E * 8 B) table reads + O(rows * f) partial-sum scatter instead of
O(E * f) scattered row reads, and the access pattern is streaming. This is
the TPU analog of the reference's shared-memory tiling in its optimized
CUDA aggregation kernel (cuda/ntsCUDAFuseKernel.cuh:154-208, block-local
accumulation) — re-derived for a memory system where the win comes from
keeping the GATHER SOURCE on-chip rather than the accumulator.

Layout (round-2 redesign): the first version gave each tile its own
EllBuckets with tile-specific level structure, unrolled in Python — at
Reddit scale the resulting program had hundreds of heterogeneous fusion
regions and took 44 MINUTES to compile (docs/PERF.md section 3c). The
production layout is UNIFORM across tiles: global power-of-two degree
levels, each level one stacked [T, N_l, K_l] table padded to the max
per-tile row count, and aggregation is ONE ``lax.scan`` over tiles — the
compiled program is a single tile body, independent of T. Two structural
bonuses fall out:

- no supernode bucket: a destination's per-tile in-degree is bounded by
  ``vt``, so K_l <= next_pow2(vt) — the power-law hub that forces the
  plain layout's K ~ 2^21 level (and its K-chunked scan) cannot occur;
- rows exist only where a (tile, dst) pair has edges: the per-tile scatter
  touches len(rows) destinations, not V, and padding rows carry
  ``dst = v_num`` and are dropped by the scatter (mode="drop").

Forward/backward pairing follows ops/ell.py exactly: the backward is the
same blocked op over the transposed (CSR) adjacency, tiled by the original
destination side, wrapped in one ``custom_vjp``. Numeric policy matches
ops.ell.ell_tables_aggregate: f32 products, f32 accumulation (both the
per-row K-reduction and the cross-tile scatter accumulator), one cast at
the end. Byte budget: the [rows, K, f] gather intermediate is bounded by
the same NTS_ELL_CHUNK_MIB budget, chunking level rows with an inner scan.

Distributed use (round 3): the layout is rectangular — ``src_num`` may
exceed ``v_num`` — so a device can aggregate its vp destination rows
from the [P*vp] all_gathered source space (parallel/dist_blocked.py
stacks per-device tables; KERNEL_TILE:vt on the dist trainers). Both
scans peel their first iteration so the accumulator carry is varying
under shard_map (the ops/aggregate._scatter_accumulate move).

Enable per-trainer with ``OPTIM_KERNEL:1`` + ``KERNEL_TILE:<vt>`` (cfg), or
pass a ``BlockedEllPair`` anywhere a graph/EllPair is accepted by
ops.aggregate.gather_dst_from_src.
"""

from __future__ import annotations

import dataclasses
import os
from typing import List

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from neutronstarlite_tpu.graph.storage import CSCGraph
from neutronstarlite_tpu.ops.ell import DEFAULT_SLOT_CHUNK, _chunk_budget_bytes
from neutronstarlite_tpu.utils.logging import get_logger

log = get_logger("blocked_ell")

_MIN_K = 4


def resolve_levels(levels: str = "") -> str:
    """Level-construction mode for the stacked tables: ``pow2`` (the
    original ladder — K = next power of two of each (tile, dst) run) or
    ``binned`` (Accel-GCN-style degree binning: K values are the observed
    run-length quantiles rounded up to ``_MIN_K`` multiples, so a skewed
    graph's rows don't pad to the pow2 ceiling — a 130-edge run lands in a
    132-slot row, not 256). ``""`` resolves NTS_ELL_LEVELS then ``pow2``
    (the fused edge path defaults to ``binned`` at its call site)."""
    lv = levels or os.environ.get("NTS_ELL_LEVELS", "") or "pow2"
    if lv not in ("pow2", "binned"):
        raise ValueError(
            f"ELL level mode must be pow2 or binned, got {lv!r} "
            "(NTS_ELL_LEVELS or the build's levels= argument)"
        )
    return lv


def _binned_row_k(
    row_len: np.ndarray, row_tile: np.ndarray, n_tiles: int
) -> np.ndarray:
    """Per-row level capacity, degree-binned (Accel-GCN's bucketing idea
    re-derived for the stacked-tile layout). Start from the pow2 ladder's
    degree bins, then fit each bin's capacity to the DATA:

    - a bin's K shrinks from the pow2 ceiling to its observed max run
      rounded up to a ``_MIN_K`` multiple (a skewed graph whose hub bin
      holds runs of <= 130 pads rows to 132 slots, not 256);
    - a bin splits at its row-count median when the split saves >= 25%
      of the bin's slots PRICED ON THE STACKED ALLOCATION — a level
      costs n_tiles * max-rows-in-any-one-tile * K, so a candidate split
      whose halves concentrate in different tiles (each new level paying
      its own per-tile max) prices high and is rejected.

    Every row's capacity is <= its pow2 ceiling, shrinking and merging
    only reduce a level's stacked cost, and splits fire only when the
    stacked cost drops — so the total padded slots are never worse than
    pow2 BY CONSTRUCTION (regression-tested, including the adversarial
    tile-skew case), while the level count grows at most 2x."""
    lens = np.maximum(row_len.astype(np.int64), 1)
    tiles = row_tile.astype(np.int64)
    pow2 = np.maximum(
        2 ** np.ceil(np.log2(lens)).astype(np.int64), _MIN_K
    )
    up = lambda v: max(int(-(-int(v) // _MIN_K) * _MIN_K), _MIN_K)

    def tile_rows(mask):
        """max rows any one tile contributes — the n_l a level of these
        rows allocates (times n_tiles * K, constant across candidates)."""
        return (
            int(np.bincount(tiles[mask], minlength=n_tiles).max())
            if mask.any()
            else 0
        )

    out = np.empty_like(lens)
    for K in np.unique(pow2):
        sel = pow2 == K
        lb = lens[sel]
        mx = up(lb.max())
        med = up(np.median(lb))
        if med < mx:
            low = sel & (lens <= med)
            cost_split = tile_rows(low) * med + tile_rows(sel & ~low) * mx
            if cost_split <= 0.75 * tile_rows(sel) * mx:
                out[sel] = np.where(lb <= med, med, mx)
                continue
        out[sel] = mx
    return out


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class BlockedEll:
    """One direction's source-tiled stacked tables.

    Per level l: ``nbr[l]`` [T, N_l, K_l] tile-local neighbor ids,
    ``wgt[l]`` [T, N_l, K_l] weights (0 on padding slots), ``dst_row[l]``
    [T, N_l] global destination of each row (``v_num`` on padding rows —
    dropped by the scatter). Rows are sorted by destination within each
    (tile, level) and unique there (a dst's whole in-tile run lives in
    exactly one level), so the scatter carries sorted+unique flags.
    """

    nbr: List[jax.Array]
    wgt: List[jax.Array]
    dst_row: List[jax.Array]
    vt: int = dataclasses.field(metadata=dict(static=True))
    v_num: int = dataclasses.field(metadata=dict(static=True))
    n_tiles: int = dataclasses.field(metadata=dict(static=True))
    # source-space row count when it differs from the destination space —
    # the distributed path aggregates a device's vp destination rows from
    # the [P*vp] all_gathered source space (parallel/dist_blocked.py)
    src_num: int = dataclasses.field(default=0, metadata=dict(static=True))

    @staticmethod
    def build(
        v_num: int,
        offsets: np.ndarray,  # [V+1] per-dst adjacency offsets
        adj: np.ndarray,  # [E] source ids, grouped by dst
        weights: np.ndarray,  # [E]
        vt: int,
        slot_chunk: int = DEFAULT_SLOT_CHUNK,  # kept for API compat; byte
        # budget (NTS_ELL_CHUNK_MIB) governs chunking at trace time
        src_num: int | None = None,  # source rows (default: square, = v_num)
        log_stats: bool = True,  # the ring builder runs P*P tiny builds and
        # logs ONE consolidated line itself (parallel/dist_ring_blocked.py)
        levels: str = "",  # "" -> NTS_ELL_LEVELS / pow2; "binned" = degree-
        # binned K values from the run-length distribution (resolve_levels)
    ) -> "BlockedEll":
        from neutronstarlite_tpu import native as native_rt

        levels = resolve_levels(levels)
        src_num = v_num if src_num is None else int(src_num)
        n_tiles = -(-src_num // vt)
        # int32 fast path: with T*V < 2^31 the (tile, dst) key fits int32,
        # halving the memory traffic of every pass AND letting numpy's
        # stable sort use its integer radix path — measured ~2x on the
        # full-scale 114.6M-edge build (1-core rig)
        idx_t = (
            np.int32
            if max(n_tiles * v_num, src_num) < 2**31
            else np.int64
        )
        deg = np.diff(offsets).astype(np.int64)
        dst_of_edge = np.repeat(np.arange(v_num, dtype=idx_t), deg)
        adj = np.asarray(adj, dtype=idx_t)
        weights = np.asarray(weights)
        if len(adj) == 0:
            return BlockedEll(
                nbr=[], wgt=[], dst_row=[],
                vt=int(vt), v_num=int(v_num), n_tiles=int(n_tiles),
                src_num=src_num,
            )

        # sort edges by (source tile, dst): edges arrive row-grouped
        # (offsets order), so ONE stable pass by tile yields (tile, row)
        # order — O(E) native counting sort, or numpy's stable sort over
        # the combined key as the fallback
        tile_of_edge = adj // np.asarray(vt, idx_t)
        use_native = native_rt.available()
        if use_native:
            order = native_rt.sort_by_tile(
                tile_of_edge.astype(np.int32, copy=False), n_tiles
            )
        else:
            key = tile_of_edge * np.asarray(v_num, idx_t) + dst_of_edge
            order = np.argsort(key, kind="stable")
        tile_sorted = tile_of_edge[order]
        dst_sorted = dst_of_edge[order]
        # sorted: extract (tile, dst) runs with one linear pass
        change = (tile_sorted[1:] != tile_sorted[:-1]) | (
            dst_sorted[1:] != dst_sorted[:-1]
        )
        bounds = np.nonzero(np.concatenate([[True], change]))[0]
        row_start = bounds
        row_len = np.diff(np.concatenate([bounds, [len(order)]]))
        row_tile = tile_sorted[bounds].astype(np.int64)
        row_dst = dst_sorted[bounds].astype(np.int64)

        # uniform global levels. pow2: K in {4, 8, ..., next_pow2(max run)}
        # (bounded by next_pow2(vt) since an in-tile run can't exceed vt);
        # binned: K at run-length quantiles (_binned_row_k) — same stacked
        # layout and invariants, only the per-level capacities differ
        if levels == "binned":
            row_k = _binned_row_k(row_len, row_tile, n_tiles)
        else:
            row_k = np.maximum(
                2 ** np.ceil(np.log2(np.maximum(row_len, 1))).astype(np.int64),
                _MIN_K,
            )
        src_local = (adj - tile_of_edge * np.asarray(vt, idx_t))[order]
        w_sorted = weights[order]
        if use_native:
            src_local = src_local.astype(np.int32, copy=False)
            w_sorted = np.ascontiguousarray(w_sorted, np.float32)

        nbrs, wgts, dsts = [], [], []
        pad_slots = real_slots = 0
        # one stacked level per DISTINCT capacity (pow2 visits the same set
        # its ladder would; binned visits the quantile capacities)
        for K in sorted(int(k) for k in np.unique(row_k)):
            sel = np.nonzero(row_k == K)[0]
            if len(sel):
                t_sel = row_tile[sel]
                counts = np.bincount(t_sel, minlength=n_tiles)
                n_l = int(counts.max())
                nbr = np.zeros((n_tiles, n_l, K), dtype=np.int32)
                wgt = np.zeros((n_tiles, n_l, K), dtype=np.float32)
                dstr = np.full((n_tiles, n_l), v_num, dtype=np.int32)
                # slot of each row inside its tile = rank among the tile's
                # rows (sel is sorted by (tile, dst), so ranks preserve the
                # per-tile dst order -> sorted scatter indices)
                starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
                slot = np.arange(len(sel)) - starts[t_sel]
                d = row_len[sel]
                if use_native:
                    native_rt.fill_blocked_level(
                        row_start[sel], d, t_sel.astype(np.int32),
                        row_dst[sel].astype(np.int32), slot, n_l, K,
                        src_local, w_sorted, nbr, wgt, dstr,
                    )
                else:
                    lo = row_start[sel]
                    k = np.arange(K)
                    valid = k[None, :] < d[:, None]
                    flat_idx = (lo[:, None] + k[None, :])[valid]
                    ti = np.broadcast_to(t_sel[:, None], (len(sel), K))[valid]
                    si = np.broadcast_to(slot[:, None], (len(sel), K))[valid]
                    ki = np.broadcast_to(k, (len(sel), K))[valid]
                    nbr[ti, si, ki] = src_local[flat_idx]
                    wgt[ti, si, ki] = w_sorted[flat_idx]
                    dstr[t_sel, slot] = row_dst[sel]
                nbrs.append(nbr)
                wgts.append(wgt)
                dsts.append(dstr)
                pad_slots += n_tiles * n_l * K - int(d.sum())
                real_slots += int(d.sum())
        if real_slots and log_stats:
            log.info(
                "blocked ELL: %d tiles of %d, %d levels, padding waste %.2fx "
                "(%d real / %d padded slots)",
                n_tiles, vt, len(nbrs), (real_slots + pad_slots) / real_slots,
                real_slots, pad_slots,
            )
        return BlockedEll(
            nbr=[jnp.asarray(n) for n in nbrs],
            wgt=[jnp.asarray(w) for w in wgts],
            dst_row=[jnp.asarray(d) for d in dsts],
            vt=int(vt),
            v_num=int(v_num),
            n_tiles=int(n_tiles),
            src_num=src_num,
        )

    def aggregate(self, x: jax.Array) -> jax.Array:
        """out[v] = sum over in-edges of w * x[src]; [S, f] -> [V, f]
        (S = src_num; square S == V on the single-chip path).

        One lax.scan over tiles; the carry is the [V, f] f32 accumulator
        (a vertex whose in-neighbors span many tiles must not round T
        times in a narrow dtype). Per level the [rows, K, f] gather
        intermediate is byte-bounded by chunking rows with an inner scan.

        shard_map compatibility (the round-2 "varying-carry peel" note):
        both scans peel their FIRST iteration outside the loop — under
        shard_map a zeros-initialized carry is unvarying over the mesh
        axis while the body's output (which mixes in sharded tables) is
        varying, and lax.scan requires carry-in == carry-out varying
        types. One data-dependent update before each scan makes the carry
        varying without naming the mesh axis here (the same move as
        ops/aggregate._scatter_accumulate, so this op runs identically
        inside and outside shard_map)."""
        acc = jnp.zeros((self.v_num, x.shape[1]), jnp.float32)
        return self.aggregate_into(acc, x).astype(x.dtype)

    def aggregate_into(self, acc: jax.Array, x: jax.Array) -> jax.Array:
        """``aggregate`` over an EXISTING [V, f] f32 accumulator, returned
        un-cast — the ring-pipelined distributed path
        (parallel/dist_ring_blocked.py) adds one source partition's
        contribution per ring step into the same f32 carry, so the
        cross-step sum never rounds in a narrow dtype."""
        f = x.shape[1]
        src_num = self.src_num or self.v_num
        v_pad = self.n_tiles * self.vt - src_num
        xt = jnp.pad(x, ((0, v_pad), (0, 0))).reshape(self.n_tiles, self.vt, f)
        budget = _chunk_budget_bytes()

        def level_add(acc, x_tile, nbr, wgt, dstr):
            n_l, K = nbr.shape
            rows = max(budget // (K * f * 4), 1)

            def chunk_add(a, chunk):
                nb, wg, dr = chunk
                vals = x_tile[nb].astype(jnp.float32) * wg[:, :, None]
                return a.at[dr].add(
                    vals.sum(axis=1),
                    indices_are_sorted=True,
                    unique_indices=True,
                    mode="drop",  # padding rows carry dst = v_num
                ), None

            if n_l <= rows:
                acc, _ = chunk_add(acc, (nbr, wgt, dstr))
                return acc
            n_ch = -(-n_l // rows)
            pad = n_ch * rows - n_l
            nb = jnp.pad(nbr, ((0, pad), (0, 0))).reshape(n_ch, rows, K)
            wg = jnp.pad(wgt, ((0, pad), (0, 0))).reshape(n_ch, rows, K)
            dr = jnp.pad(
                dstr, (0, pad), constant_values=self.v_num
            ).reshape(n_ch, rows)
            # first chunk outside the scan (varying-carry peel, see above)
            acc, _ = chunk_add(acc, (nb[0], wg[0], dr[0]))
            if n_ch > 1:
                acc, _ = lax.scan(chunk_add, acc, (nb[1:], wg[1:], dr[1:]))
            return acc

        def body(acc, xs):
            x_tile, tables = xs
            for nbr, wgt, dstr in tables:
                acc = level_add(acc, x_tile, nbr, wgt, dstr)
            return acc, None

        tables = list(zip(self.nbr, self.wgt, self.dst_row))
        if not tables:
            return acc
        # first tile outside the scan (varying-carry peel, see above)
        acc, _ = body(acc, (xt[0], [(n[0], w[0], d[0]) for n, w, d in tables]))
        if self.n_tiles > 1:
            rest = [(n[1:], w[1:], d[1:]) for n, w, d in tables]
            acc, _ = lax.scan(body, acc, (xt[1:], rest))
        return acc


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class BlockedEllPair:
    """Forward (CSC, tiled by src) + backward (CSR, tiled by dst) tables."""

    fwd: BlockedEll
    bwd: BlockedEll

    @staticmethod
    def from_host(
        g: CSCGraph, vt: int, slot_chunk: int = DEFAULT_SLOT_CHUNK
    ) -> "BlockedEllPair":
        fwd = BlockedEll.build(
            g.v_num, g.column_offset, g.row_indices, g.edge_weight_forward,
            vt, slot_chunk,
        )
        bwd = BlockedEll.build(
            g.v_num, g.row_offset, g.column_indices, g.edge_weight_backward,
            vt, slot_chunk,
        )
        return BlockedEllPair(fwd=fwd, bwd=bwd)

    def gather_dst_from_src(self, x: jax.Array) -> jax.Array:
        """Source-tiled weighted aggregation (custom_vjp pairs the transpose)."""
        return _blocked_aggregate(self.fwd, self.bwd, x)

    def gather_src_from_dst(self, y: jax.Array) -> jax.Array:
        """The CSR direction as a forward op."""
        return _blocked_aggregate(self.bwd, self.fwd, y)

    def describe(self) -> str:
        return (
            f"blocked ELL aggregation ({self.fwd.n_tiles} src tiles of "
            f"{self.fwd.vt} vertices, {len(self.fwd.nbr)} stacked levels)"
        )


@jax.custom_vjp
def _blocked_aggregate(fwd: BlockedEll, bwd: BlockedEll, x: jax.Array):
    return fwd.aggregate(x)


def _blocked_aggregate_fwd(fwd, bwd, x):
    return fwd.aggregate(x), (fwd, bwd)


def _blocked_aggregate_bwd(res, g):
    from neutronstarlite_tpu.ops.segment import zero_cotangent

    fwd, bwd = res
    zero = jax.tree.map(zero_cotangent, (fwd, bwd))
    return (*zero, bwd.aggregate(g))


_blocked_aggregate.defvjp(_blocked_aggregate_fwd, _blocked_aggregate_bwd)


def blocked_gather_dst_from_src(pair: BlockedEllPair, x: jax.Array) -> jax.Array:
    return pair.gather_dst_from_src(x)


def blocked_gather_src_from_dst(pair: BlockedEllPair, y: jax.Array) -> jax.Array:
    return pair.gather_src_from_dst(y)
