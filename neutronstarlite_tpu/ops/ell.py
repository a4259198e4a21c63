"""ELL-bucketed neighbor aggregation: the gather-only TPU hot path.

The reference's optimized CUDA aggregation
(``aggregate_kernel_from_src_with_weight_optim_nts``,
cuda/ntsCUDAFuseKernel.cuh:154-208, enabled by the ``OPTIM_KERNEL`` cfg flag)
packs multiple destination vertices per thread block and accumulates in
shared memory — its win is turning scattered global-memory accumulation into
block-local accumulation. The TPU analog must go further: TPU has no fast
scatter at all (XLA lowers scatter-add to a serialized update stream), while
*gather* is vectorized and fast. So the production layout removes the
scatter entirely:

- Vertices are grouped into in-degree buckets ("levels") of widths K_0 <
  K_1 < ... (each 4, a multiple of 8 or, above 8192, a power of two; the
  last at least the largest degree); each bucket stores a padded dense neighbor table ``nbr [Nk, K]``
  + ``wgt [Nk, K]`` (ELLPACK slices, degree-sorted). A padding slot is
  gathered and multiplied like a real one, so the widths are chosen per
  graph: ``level_widths`` takes the degree histogram and returns the at
  most ``MAX_LEVELS`` widths that hold the fewest slots (a power-of-two
  ladder wasted up to 2x and 43-50% on the benchmark's graphs; the chosen
  widths 16-25%, PERF.md PR 29). The stacked distributed tables
  (parallel/dist_ell.py) call the same function with every device's
  degrees, so a level is priced at its fullest device's rows.
- Aggregation for a bucket is ``out[r] = sum_k wgt[r,k] * x[nbr[r,k]]`` —
  one gather plus a dense masked reduction, both native TPU operations; row
  chunks bound the [rows, K, f] gather intermediate in VMEM-friendly sizes.
- Results are assembled with one inverse-permutation gather (vertices were
  regrouped by bucket).

The backward needs grad_x[u] = sum over out-edges (u -> v) of w * g[v]: the
same operation over the transposed adjacency, so ``EllPair`` precomputes
forward (in-edge) and backward (out-edge) bucket tables and pairs them in a
``custom_vjp`` — exactly the reference's CSC-forward/CSR-backward kernel
pairing (GatherByDstFromSrc / GatherBySrcFromDst, NtsScheduler.hpp:151/:257).
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from neutronstarlite_tpu.graph.storage import CSCGraph

# legacy upper cap on slots (rows * K) per scan step. Chunk sizing is now
# governed by the BYTE budget below (min(slot_chunk, slot_budget) — at the
# default 32 MiB budget and f >= 8 the byte bound is always the tighter
# one); the slot cap survives only as a table-layout knob for tests that
# force specific chunk counts.
DEFAULT_SLOT_CHUNK = 1 << 21

# Most levels one direction's tables may have: each level is one more
# gather/reduce shape in every program that aggregates, and a shape costs
# set-up. Sized on the two benchmark graphs (PERF.md, PR 29; my chip runs):
# at 32 the Reddit cell's tables held 1.128 slots an edge and its warm
# set-up rose by 2.4-3.0 s of 27-31 (0.13-0.16 s a level over the ladder's
# 14: program load at the first step, the eval and the input aggregate),
# against a bound of 10%; at 24 they hold 1.163 (3% more slots) for 1.3-1.7 s.
MAX_LEVELS = 24
# Slots of one row chunk as level_widths prices it: the default byte budget
# at a hidden width of 128 in f32 (half of it at 256; the choice hardly
# moves with it, the padding it prices is under 1% of the slots).
_PRICED_CHUNK_SLOTS = (32 << 20) // (4 * 128)
# Above this width a level's width stays a power of two. Such a level can
# exceed the byte budget of one gather (K > 32 MiB / 4f: 13,934 at f = 602)
# and then takes ``ell_tables_aggregate``'s K-chunked path. On the chip that
# path, under bf16 reads inside a many-level program, gave sums that were
# not finite where K was no power of two ([6, 227768] and [9, 161560] were
# wrong even alone; f32 reads and every narrower level were right), and the
# training loss with them. With K a power of two, as the ladder had it, the
# runs are finite and pass the benchmark's check, though a throwaway script
# found rows of such a level too large there as well, in the parent's
# tables as in these: the fault is open (PERF.md section 7, PR 29). The
# levels that wide hold a few dozen rows: the ladder there costs 2% of the
# benchmark's slots.
_POW2_WIDTHS_ABOVE = 8192
# Above this width the candidate widths thin to one per 64th of an octave
# (1.1% steps): finer ones save nothing measurable and the search is
# quadratic in their number.
_EXACT_WIDTHS_UP_TO = 512


def _aligned_width(deg: np.ndarray) -> np.ndarray:
    """Smallest table width that holds ``deg`` neighbours: 4, or a multiple
    of 8 (the f32 sublane count of the [rows, K, f] slab), or above
    ``_POW2_WIDTHS_ABOVE`` a power of two."""
    deg = np.asarray(deg, dtype=np.int64)
    width = np.where(deg <= 4, 4, -(-deg // 8) * 8)
    pow2 = 1 << np.ceil(np.log2(np.maximum(width, 1))).astype(np.int64)
    return np.where(width > _POW2_WIDTHS_ABOVE, pow2, width)


def _chunk_rows(slots: int, K) -> np.ndarray:
    """Rows of one scan step over a level of width ``K`` under a budget of
    ``slots`` slots: a multiple of 8 once there are 8 (kind to the
    compiler's tiling when K is no power of two), never below 1."""
    rows = np.maximum(slots // np.maximum(K, 1), 1)
    return np.where(rows >= 8, rows // 8 * 8, rows)


def _rows_at_most(degs, widths) -> np.ndarray:
    """[device, width] count of the device's rows with ``0 < deg <= width``."""
    return np.stack([
        np.searchsorted(np.sort(d[d > 0]), widths, side="right") for d in degs
    ])


def _priced(K, rows):
    """Slots of one level as the program walks it: whole row chunks where
    the level is scanned (``ell_tables_aggregate`` pads the last one)."""
    chunk = _chunk_rows(_PRICED_CHUNK_SLOTS, K)
    return K * np.where(rows <= chunk, rows, -(-rows // chunk) * chunk)


def level_slots(widths, degrees_per_device) -> int:
    """What ``level_widths`` minimises: the slots the program walks for
    these level widths over these devices' degree arrays. Per level ``K x
    rows``, rows the fullest device's count of ``K_prev < deg <= K``
    (stacked tables pad every device to it), in whole row chunks. Zero
    degrees hold no slot."""
    widths = np.asarray(widths, dtype=np.int64)
    degs = [np.asarray(d, dtype=np.int64) for d in degrees_per_device]
    if widths.size == 0 or not degs:
        return 0
    rows = np.diff(_rows_at_most(degs, widths), axis=1, prepend=0).max(axis=0)
    return int(_priced(widths, rows).sum())


def level_widths(degrees_per_device, max_levels: int = MAX_LEVELS) -> np.ndarray:
    """Level widths ``K_0 < K_1 < ...`` for the tables of the devices that
    share one stacked layout (a list of one degree array for
    ``EllBuckets.build``): each an ``_aligned_width`` (4, a multiple of 8,
    a power of two above ``_POW2_WIDTHS_ABOVE``), the last at least the
    largest degree, at most ``max_levels`` of them, and among those the set
    that minimises ``level_slots``. Found exactly, by dynamic programming
    over the degree histogram: ``best[l][j]`` is the cheapest way to cover
    every degree up to candidate ``j`` with ``l`` levels, the last of width
    ``j``. Candidates are the aligned ceilings of the degrees that occur,
    above ``_EXACT_WIDTHS_UP_TO`` only the largest in each 64th of an
    octave. Zero degrees are not covered (they hold no slot); every level
    returned holds a row on some device. Empty input gives no width."""
    degs = [np.asarray(d, dtype=np.int64) for d in degrees_per_device]
    degs = [d[d > 0] for d in degs]
    if not any(d.size for d in degs):
        return np.zeros(0, dtype=np.int64)
    cand = np.unique(_aligned_width(np.concatenate(degs)))
    step = np.ceil(np.log2(cand) * 64)
    last_of_step = np.append(step[1:] != step[:-1], True)
    cand = cand[(cand <= _EXACT_WIDTHS_UP_TO) | last_of_step]
    m = cand.size
    # below[p, j]: device p's rows of degree <= cand[j]; column 0 is "none"
    below = np.pad(_rows_at_most(degs, cand), ((0, 0), (1, 0)))
    # cost[i, j]: one level of width cand[j] over the degrees above cand[i-1]
    rows = (below[:, None, 1:] - below[:, :m, None]).max(axis=0)
    inf = np.iinfo(np.int64).max // 4
    cost = np.where(
        np.arange(m)[:, None] <= np.arange(m)[None, :], _priced(cand[None, :], rows), inf
    )
    best = cost[0].copy()  # one level: everything up to j at width cand[j]
    came = [np.zeros(m, dtype=np.int64)]
    totals = [best[-1]]
    for _ in range(1, min(max_levels, m)):
        # the level before ends at candidate i-1, i in 1..j
        via = best[:-1, None] + cost[1:, :]
        came.append(via.argmin(axis=0))
        best = np.minimum(via.min(axis=0), inf)
        totals.append(best[-1])
    n = int(np.argmin(totals))  # the fewest levels among the cheapest
    picked, j = [], m - 1
    for l in range(n, -1, -1):
        picked.append(j)
        j = int(came[l][j]) if l else -1
    widths = cand[picked[::-1]]
    held = np.diff(below[:, np.asarray(picked[::-1]) + 1], axis=1, prepend=0).max(axis=0)
    return widths[held > 0]


# Byte budget for one [rows, K, f] gather intermediate. At the default
# 2^21-SLOT chunk the full-scale intermediates (512 MiB at f=128) were
# materialized to HBM by the compiler; bounding them to ~32 MiB keeps them
# VMEM-resident in the compiled v5e module (docs/PERF.md section 3a).
# Width-aware (slots alone don't bound bytes when f varies 41..602).
# Override for on-chip tuning: NTS_ELL_CHUNK_MIB.
DEFAULT_CHUNK_MIB = 32


def _chunk_budget_bytes() -> int:
    """Read NTS_ELL_CHUNK_MIB (clamped to >= 1 MiB; non-numeric falls back
    to the default). TRACE-TIME semantics: the value is baked into the
    traced program, so changing the env after a jit cache is warm has no
    effect — set it before the first compile."""
    import os

    raw = os.environ.get("NTS_ELL_CHUNK_MIB", "")
    try:
        mib = int(raw) if raw else DEFAULT_CHUNK_MIB
    except ValueError:
        mib = DEFAULT_CHUNK_MIB
    return max(mib, 1) << 20


def ell_tables_aggregate(x, nbrs, wgts, slot_chunk: int, out_dtype=None) -> jax.Array:
    """Shared per-level ELL reduction: concat over levels of
    ``sum_k wgt[r, k] * x[nbr[r, k]]`` (callers apply their own inv_perm).
    Single source of the numeric policy for EllBuckets.aggregate AND the
    distributed DistEll._local_aggregate — the K-reduction accumulates in
    f32 regardless of x.dtype (the fused multiply-reduce holds its
    accumulator in registers, so wide accumulation costs no HBM traffic):
    bf16 reads keep the bandwidth win while degree-500 sums keep ~f32
    accuracy, the same policy as the reference's CUDA kernel whose
    shared-memory accumulator is float (cuda/ntsCUDAFuseKernel.cuh:147-208).

    The [rows, K, f] gather intermediate is bounded in BYTES (width-aware,
    see DEFAULT_CHUNK_MIB) by chunking rows — and, for the few-row hub
    levels whose K alone exceeds the budget (a 2^21-degree supernode at
    f=602 is a 2.4 GiB slab), by scanning K column chunks with an f32
    running sum. Chunk boundaries never split a row's K-reduction across
    different precisions, so results are invariant to the chunking.

    ``out_dtype``: result dtype (default x.dtype). Callers that keep
    accumulating across calls (the blocked source-tiled layout) pass
    float32 so the cross-call sum stays wide too. A level with K == 0
    (the zero-degree bucket) yields zero rows without any gather."""
    f = x.shape[1]
    out_dtype = out_dtype or x.dtype
    budget = _chunk_budget_bytes()
    # the chunk intermediate lives in f32 whatever x.dtype is (the upcast
    # below) — size the slot budget for the f32 slab, not the input bytes
    slot_budget = max(budget // (f * max(x.dtype.itemsize, 4)), 1)

    def partial_f32(nbr, wgt):
        # products AND accumulation in f32 (register-resident in the fused
        # reduce, so no extra HBM traffic; bf16 only on the gather reads) —
        # the ONE copy of the numeric policy
        vals = x[nbr].astype(jnp.float32) * wgt[:, :, None]
        return vals.sum(axis=1)

    def row_sum(nbr, wgt):
        return partial_f32(nbr, wgt).astype(out_dtype)

    def k_chunked_sum(nbr, wgt):
        # K exceeds the per-chunk slot budget (hub levels); scan K column
        # chunks with an f32 running sum (padding columns carry weight 0)
        Nk, K = nbr.shape
        kc = max(slot_budget // max(Nk, 1), 1)
        n_ch = -(-K // kc)
        pad = n_ch * kc - K
        nb = jnp.pad(nbr, ((0, 0), (0, pad))).reshape(Nk, n_ch, kc)
        wg = jnp.pad(wgt, ((0, 0), (0, pad))).reshape(Nk, n_ch, kc)
        nb_t = nb.transpose(1, 0, 2)
        wg_t = wg.transpose(1, 0, 2)

        # first chunk outside the scan: a zeros-initialized carry is
        # unvarying over the mesh axis under shard_map while the body's
        # output is varying, and lax.scan requires carry-in == carry-out
        # varying types (the round-1 ring bug class; same peel as
        # ops/aggregate._scatter_accumulate)
        acc = partial_f32(nb_t[0], wg_t[0])
        if n_ch > 1:

            def body(acc, chunk):
                n, w = chunk
                return acc + partial_f32(n, w), None

            acc, _ = lax.scan(body, acc, (nb_t[1:], wg_t[1:]))
        return acc.astype(out_dtype)

    outs = []
    for nbr, wgt in zip(nbrs, wgts):
        Nk, K = nbr.shape
        if K == 0:
            outs.append(jnp.zeros((Nk, f), out_dtype))
            continue
        if K > slot_budget:
            # rows-of-1 chunks would still breach the byte bound; chunk K
            outs.append(k_chunked_sum(nbr, wgt))
            continue
        rows = int(_chunk_rows(min(slot_chunk, slot_budget), K))
        if Nk <= rows:
            outs.append(row_sum(nbr, wgt))
            continue
        n_ch = -(-Nk // rows)
        pad = n_ch * rows - Nk
        nb = jnp.pad(nbr, ((0, pad), (0, 0))).reshape(n_ch, rows, K)
        wg = jnp.pad(wgt, ((0, pad), (0, 0))).reshape(n_ch, rows, K)

        def body(_, chunk):
            n, w = chunk
            return 0, row_sum(n, w)

        _, out = lax.scan(body, 0, (nb, wg))
        outs.append(out.reshape(n_ch * rows, f)[:Nk])
    return jnp.concatenate(outs, axis=0)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class EllBuckets:
    """One direction's degree-bucketed neighbor tables.

    ``nbr[i]`` [Nk, K_i] neighbor ids, ``wgt[i]`` [Nk, K_i] weights (0 on
    padding, padding neighbors point at vertex 0), ``inv_perm`` [V] maps
    global vertex id -> row in the bucket-ordered concatenation.
    """

    nbr: List[jax.Array]
    wgt: List[jax.Array]
    inv_perm: jax.Array
    v_num: int = dataclasses.field(metadata=dict(static=True))
    slot_chunk: int = dataclasses.field(metadata=dict(static=True))

    @staticmethod
    def build(
        v_num: int,
        offsets: np.ndarray,  # [V+1] per-vertex adjacency offsets
        adj: np.ndarray,  # [E] neighbor ids, grouped by vertex
        weights: np.ndarray,  # [E]
        slot_chunk: int = DEFAULT_SLOT_CHUNK,
    ) -> "EllBuckets":
        deg = np.diff(offsets).astype(np.int64)
        order = np.argsort(deg, kind="stable")
        sdeg = deg[order]
        nbrs, wgts, perm_parts = [], [], []
        i = 0
        # zero-degree rows get a K=0 bucket: no slots, no gather work —
        # essential for the blocked source-tiled layout where most rows
        # have no edge in a given tile (power-law sparsity)
        j0 = int(np.searchsorted(sdeg, 0, side="right"))
        if j0 > 0:
            ids = order[:j0]
            nbrs.append(np.zeros((j0, 0), dtype=np.int32))
            wgts.append(np.zeros((j0, 0), dtype=np.float32))
            perm_parts.append(ids)
            i = j0
        from neutronstarlite_tpu import native as native_rt

        use_native = native_rt.available()
        if use_native:
            adj32 = np.ascontiguousarray(adj, np.int32)
            w32 = np.ascontiguousarray(weights, np.float32)
        for K in level_widths([deg]).tolist():
            j = int(np.searchsorted(sdeg, K, side="right"))
            ids = order[i:j]
            Nk = len(ids)
            nbr = np.zeros((Nk, K), dtype=np.int32)
            wgt = np.zeros((Nk, K), dtype=np.float32)
            lo = offsets[ids]
            d = deg[ids]
            if use_native:
                # C fill of the ragged runs (nts_fill_blocked_level with a
                # single "tile"; the dst/slot channel is the row index) —
                # the same routine the blocked layout uses
                dstr = np.empty((1, Nk), np.int32)
                native_rt.fill_blocked_level(
                    lo, d, np.zeros(Nk, np.int32), ids.astype(np.int32),
                    np.arange(Nk, dtype=np.int64), Nk, K, adj32, w32,
                    nbr.reshape(1, Nk, K), wgt.reshape(1, Nk, K), dstr,
                )
            else:
                # vectorized fill: [Nk, K] table rows from ragged runs
                k = np.arange(K)
                valid = k[None, :] < d[:, None]
                flat_idx = (lo[:, None] + k[None, :])[valid]
                nbr[valid] = adj[flat_idx]
                wgt[valid] = weights[flat_idx]
            nbrs.append(nbr)
            wgts.append(wgt)
            perm_parts.append(ids)
            i = j
        perm = np.concatenate(perm_parts) if perm_parts else np.zeros(0, np.int64)
        inv = np.empty(v_num, dtype=np.int64)
        inv[perm] = np.arange(v_num)
        return EllBuckets(
            nbr=[jnp.asarray(n) for n in nbrs],
            wgt=[jnp.asarray(w) for w in wgts],
            inv_perm=jnp.asarray(inv, dtype=jnp.int32),
            v_num=v_num,
            slot_chunk=int(slot_chunk),
        )

    def aggregate(self, x: jax.Array) -> jax.Array:
        """out[v] = sum over v's table row of w * x[nbr]; [V, f] -> [V, f]."""
        return ell_tables_aggregate(x, self.nbr, self.wgt, self.slot_chunk)[
            self.inv_perm
        ]


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class EllPair:
    """Forward (in-edge/CSC) + backward (out-edge/CSR) bucket tables."""

    fwd: EllBuckets
    bwd: EllBuckets

    @staticmethod
    def from_host(g: CSCGraph, slot_chunk: int = DEFAULT_SLOT_CHUNK) -> "EllPair":
        fwd = EllBuckets.build(
            g.v_num,
            g.column_offset,
            g.row_indices,
            g.edge_weight_forward,
            slot_chunk,
        )
        bwd = EllBuckets.build(
            g.v_num,
            g.row_offset,
            g.column_indices,
            g.edge_weight_backward,
            slot_chunk,
        )
        return EllPair(fwd=fwd, bwd=bwd)

    def padding_stats(self, real_edges: int) -> dict:
        """Slot occupancy of both directions, as
        ``parallel/dist_ell.DistEllPair.padding_stats`` reports it."""
        return table_padding_stats(self.fwd.nbr, self.bwd.nbr, real_edges)

    def gather_dst_from_src(self, x: jax.Array) -> jax.Array:
        """Gather-only weighted aggregation (custom_vjp pairs the transpose)."""
        return _ell_aggregate(self.fwd, self.bwd, x)

    def gather_src_from_dst(self, y: jax.Array) -> jax.Array:
        """The CSR direction as a forward op."""
        return _ell_aggregate(self.bwd, self.fwd, y)

    def describe(self) -> str:
        return (
            f"ELL gather-only aggregation ({len(self.fwd.nbr)} fwd / "
            f"{len(self.bwd.nbr)} bwd levels)"
        )


def table_padding_stats(fwd_nbr, bwd_nbr, real_edges: int) -> dict:
    """Slots of two directions' level tables (any leading device axis
    counted) against the edges they hold: the only padding left is the
    rounding of a degree up to its level's width and, stacked, the
    fullest device's row count per level."""
    fwd = sum(math.prod(n.shape) for n in fwd_nbr)
    bwd = sum(math.prod(n.shape) for n in bwd_nbr)
    return {
        "real_edges": int(real_edges),
        "fwd_slots": fwd,
        "bwd_slots": bwd,
        "fwd_waste_ratio": fwd / max(real_edges, 1),
        "bwd_waste_ratio": bwd / max(real_edges, 1),
        "levels": max(
            sum(n.shape[-1] > 0 for n in nbrs) for nbrs in (fwd_nbr, bwd_nbr)
        ),
    }


@jax.custom_vjp
def _ell_aggregate(fwd: EllBuckets, bwd: EllBuckets, x: jax.Array) -> jax.Array:
    return fwd.aggregate(x)


def _ell_aggregate_fwd(fwd, bwd, x):
    return fwd.aggregate(x), (fwd, bwd)


def _ell_aggregate_bwd(res, g):
    from neutronstarlite_tpu.ops.segment import zero_cotangent

    fwd, bwd = res
    zero = jax.tree.map(zero_cotangent, (fwd, bwd))
    return (*zero, bwd.aggregate(g))


_ell_aggregate.defvjp(_ell_aggregate_fwd, _ell_aggregate_bwd)


def ell_gather_dst_from_src(pair: EllPair, x: jax.Array) -> jax.Array:
    return pair.gather_dst_from_src(x)


def ell_gather_src_from_dst(pair: EllPair, y: jax.Array) -> jax.Array:
    return pair.gather_src_from_dst(y)
