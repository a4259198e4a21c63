"""Block-sparse (dst-tile, src-tile) streamed Pallas aggregation.

The one fused aggregation kernel Mosaic can compile (``PALLAS:1``). A
kernel that keeps the ``[V, f]`` table (or a 128-wide column chunk of it)
resident in VMEM and gathers rows from it would be the faster design while
the table fits, but Mosaic has no in-kernel row gather, so it cannot lower
at any shape (docs/PERF.md section 5; removed in PR 31). This kernel needs
no gather and no resident table, at any V:

Neither the feature table nor a 128-wide column of it has to fit on-chip,
because the kernel streams BOTH sides: vertices are cut into destination tiles
of ``dt`` rows and source tiles of ``vt`` rows; edges are packed into
fixed-shape blocks, each block belonging to one (dst tile, src tile)
pair. The pallas grid walks blocks grouped per destination tile (each
tile's blocks CONSECUTIVE — the ordering invariant) with the [dt, f]
output tile living in VMEM across every consecutive block of its tile
(zeroed on first visit, spilled to HBM when the tile changes — the
revisiting-output accumulation pattern), while the [vt, f] source slab is
DMA-streamed per block via a scalar-prefetched block->tile map
(``pltpu.PrefetchScalarGridSpec``). HBM traffic per application:
O(E * 8 B) table reads + O(sum over dst tiles of present src tiles *
vt * f) slab streams + O(V * f) output writes — versus O(E * f) random
HBM gathers for the plain layout past VMEM.

Block layout: each block is ``R`` rows of ``K`` slots. A row is (a piece
of) one destination's in-edge run within one source tile: runs longer
than K split into several rows (legal because every row's partial sum is
accumulated). Rows store tile-LOCAL neighbor ids ``nbr`` [B, K, R] and
weights ``wgt`` [B, K, R] (the K-major layout keeps R on the 128-lane
axis), plus the row's tile-local destination ``ldst`` [B, R]. Padding
rows/slots carry weight 0 and index 0, contributing nothing.

The per-block combine is scatter-free BY CONSTRUCTION: row partial sums
``acc`` [R, f] land in the output tile through a one-hot MXU matmul —
``onehot(ldst) [dt, R] @ acc [R, f]`` — the TPU-idiomatic scatter (the
MXU is the only unit that reorders data at full bandwidth; per-row
dynamic stores would serialize). This is the kernel's cost: the matmul
spends ``dt * f * 2`` FLOPs per packed ROW (independent of K), so at
Reddit scale (~7M rows, dt=512, f=602) it burns ~4.2 TFLOP per
application; the price buys streaming locality the plain layout cannot
offer. Reference analog: the shared-memory tiled CUDA aggregation (cuda/ntsCUDAFuseKernel.cuh:154-208) — re-derived for a
memory system where the accumulator tile, not the source tile, is the
scarce on-chip resource.

Forward/backward pairing follows ops/ell.py: the backward is the same
kernel over the transposed (CSR) layout, one ``custom_vjp``. Numeric
policy: the one-hot W entries ROUND TO THE SLAB DTYPE (bf16 in
production) so the main dot runs at full MXU rate — a documented
divergence from the XLA ELL path's f32 edge weights, bounded by the
bf16 tolerance class (~5e-2 relative; on-chip check
tests/test_tpu.py::test_tpu_bsp_bf16_and_segmented) — with f32
accumulation in-block and across blocks and one cast at the end.
Off-TPU the kernel runs in interpret mode (tests).
"""

from __future__ import annotations

import dataclasses
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from neutronstarlite_tpu.graph.storage import CSCGraph
from neutronstarlite_tpu.utils.logging import get_logger

log = get_logger("bsp_ell")

DEFAULT_DT = 512  # dst tile rows (the VMEM-resident accumulator height)
DEFAULT_VT = 4096  # src tile rows (the streamed slab height)
DEFAULT_K = 8  # slots per packed row
DEFAULT_R = 128  # rows per block (the 128-lane axis of the tables)
# Max blocks per pallas_call: the [B] int32 scalar-prefetch key must fit
# SMEM (~1 MB; round-3 AOT evidence: two ~600 KB maps RESOURCE_EXHAUSTED,
# one packed ~700 KB array compiled). 224k blocks = 896 KB of keys leaves
# headroom for Mosaic's own scalars. Past this the build SEGMENTS the
# grid at dst-tile boundaries (see BspEll.build) — the compiled program
# is then V-independent and there is no block-count ceiling at all.
DEFAULT_MAX_BLOCKS = 224 * 1024


def pallas_interpret_default() -> bool:
    """interpret everywhere the default backend can't lower Mosaic — keeps
    the CPU suite exercising the same code path the chip runs.
    NTS_PALLAS_FORCE_COMPILED=1 overrides for AOT lowering against a TPU
    TOPOLOGY from a CPU host (tools/aot_bench_path): tracing never executes
    the kernel, and the topology compiler consumes the Mosaic call."""
    if os.environ.get("NTS_PALLAS_FORCE_COMPILED", "0") == "1":
        return False
    return jax.default_backend() not in ("tpu",)


def bsp_bseg_menu(cap_eff: int) -> "list[int]":
    """The EXACT b_seg menu a segmented build can emit under this cap:
    seven uniform quantum steps plus the cap itself (the quantum is
    floor(cap/8) rounded down to a multiple of 8, which need not divide
    the cap — the cap is its own 8th value). Shared with
    tools/aot_bsp_scale so the AOT proof enumerates precisely these."""
    quantum = max(8, (cap_eff // 8) - (cap_eff // 8) % 8)
    menu = [k * quantum for k in range(1, 8) if k * quantum < cap_eff]
    return menu + [cap_eff]


def bsp_tseg_menu(t_dst: int) -> "list[int]":
    """The EXACT t_seg menu a segmented build can emit for this t_dst:
    at most 16 quantum steps (quantum = ceil(hi/16) rounded up to a
    128-multiple) capped by hi = roundup128(t_dst + 1), the band bound
    (t_seg_cap <= t_dst always). Shared with tools/aot_bsp_scale so the
    AOT proof compiles precisely the (b_seg menu) x (this menu) lattice
    — an arbitrary roundup128(tiles) could land on any of ~t_dst/128
    values the tool never pre-lowered (ADVICE r4), re-exposing the
    full-scale Mosaic-compile hang the proof exists to retire. Snapping
    up wastes only per-call output-buffer rows (trailing tiles are
    never written or read): at most one quantum ~= 6% of the full
    output, and none of the compute grid, which is sized by b_seg."""
    hi = -(-(t_dst + 1) // 128) * 128
    quantum = max(128, -(-(hi // 16) // 128) * 128)
    menu = [k * quantum for k in range(1, 16) if k * quantum < hi]
    return menu + [hi]


def resolve_bsp_knobs(dt: int = 0, k_slots: int = 0) -> "tuple[int, int]":
    """Resolve the NTS_BSP_DT / NTS_BSP_K env tunables (0 = use env or
    default). Shared by the single-chip (BspEllPair.from_host) and dist
    (parallel/dist_bsp.DistBsp.build) builders so on-chip A/B knobs
    behave uniformly across paths."""
    dt = int(dt) or int(os.environ.get("NTS_BSP_DT", DEFAULT_DT))
    k_slots = int(k_slots) or int(os.environ.get("NTS_BSP_K", DEFAULT_K))
    return dt, k_slots


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class BspEll:
    """One direction's packed block tables (see module docstring)."""

    nbr: jax.Array  # [S*b_seg, K, R] int32 tile-local neighbor ids
    wgt: jax.Array  # [S*b_seg, K, R] f32 (0 on padding)
    ldst: jax.Array  # [S*b_seg, R] int32 tile-local destination row
    # ONE packed per-block tile key: dst_tile_LOCAL * t_src + src_tile.
    # The key array is the kernel's scalar-prefetch operand and lives in
    # SMEM (1 MB): two separate [B] int32 maps overflowed it at full
    # Reddit scale (B ~ 141-175k -> 552-684 KB EACH, AOT
    # RESOURCE_EXHAUSTED, docs/perf_runs/round3/aot_eager_bsp2.json);
    # packed, one array fits to ~250k blocks. Past the budget the build
    # SEGMENTS the grid: blocks are cut at dst-tile boundaries into
    # n_seg uniform calls of b_seg blocks x t_seg dst tiles each, keys
    # are segment-LOCAL, and aggregate() runs one pallas_call per
    # segment (same shapes -> ONE compiled program reused n_seg times).
    # 10x-Reddit (~1.4M blocks) therefore compiles the same program as
    # full Reddit; only the Python-level segment count grows.
    blk_key: jax.Array  # [S*b_seg] int32 packed (local dst_tile, src_tile)
    v_num: int = dataclasses.field(metadata=dict(static=True))
    dt: int = dataclasses.field(metadata=dict(static=True))
    vt: int = dataclasses.field(metadata=dict(static=True))
    # RECTANGULAR form (the distributed per-shard case: dst rows are one
    # device's vp vertices, srcs index the full all_gathered [P*vp, f]
    # slab): src_num sizes the source tiling independently of v_num.
    # 0 = square (src space == dst space), the single-chip default.
    src_num: int = dataclasses.field(default=0, metadata=dict(static=True))
    # SMEM-budget segmentation (see blk_key): n_seg calls of b_seg blocks
    # each, call s covering the contiguous dst-tile range of seg_tiles[s]
    # tiles (t_seg = the per-call OUTPUT tile count >= max(seg_tiles);
    # trailing output tiles beyond a call's real range are never written
    # or read). Defaults describe the unsegmented form (b_seg/t_seg = 0
    # -> whole table / all tiles, one call).
    n_seg: int = dataclasses.field(default=1, metadata=dict(static=True))
    b_seg: int = dataclasses.field(default=0, metadata=dict(static=True))
    t_seg: int = dataclasses.field(default=0, metadata=dict(static=True))
    seg_tiles: tuple = dataclasses.field(
        default=(), metadata=dict(static=True)
    )

    @staticmethod
    def build(
        v_num: int,
        offsets: np.ndarray,  # [V+1] per-dst adjacency offsets
        adj: np.ndarray,  # [E] source ids, grouped by dst
        weights: np.ndarray,  # [E]
        dt: int = DEFAULT_DT,
        vt: int = DEFAULT_VT,
        k_slots: int = DEFAULT_K,
        r_rows: int = DEFAULT_R,
        src_num: int = 0,  # 0 = square; else rectangular (adj < src_num)
        max_blocks: int = 0,  # 0 -> NTS_BSP_MAX_BLOCKS / DEFAULT_MAX_BLOCKS
        keep_host: bool = False,  # True: leave tables as numpy (a caller
        # that re-lays them — DistBsp's segmented stack — avoids a
        # device round-trip at exactly the scale that segments)
    ) -> "BspEll":
        K, R = int(k_slots), int(r_rows)
        max_blocks = int(max_blocks) or int(
            os.environ.get("NTS_BSP_MAX_BLOCKS", DEFAULT_MAX_BLOCKS)
        )
        n_src = int(src_num) or int(v_num)
        t_dst = -(-v_num // dt)
        t_src = -(-n_src // vt)
        e_num = len(adj)
        deg = np.diff(offsets).astype(np.int64)
        dst_of_edge = np.repeat(np.arange(v_num, dtype=np.int64), deg)
        adj = np.asarray(adj, dtype=np.int64)
        weights = np.asarray(weights, dtype=np.float32)

        if e_num:
            # group edges by (dst tile, src tile); edges arrive dst-grouped,
            # so a stable sort by the pair key keeps dst ascending per group.
            # The key space is tiny (t_dst * t_src ~ 13k at full Reddit
            # scale), so the native O(E) counting sort applies directly —
            # measured neutral on wall time at full scale (the per-edge
            # fancy-index fills dominate the build, 274 s vs 276 s) but it
            # avoids argsort's O(E) int64 temp at peak
            from neutronstarlite_tpu import native as native_rt

            key = (dst_of_edge // dt) * t_src + adj // vt
            # key-space bound: the counting sort allocates an int64
            # histogram of t_dst * t_src entries — past ~16M keys (128 MB)
            # argsort is the better trade, long before the int32 limit
            if native_rt.available() and t_dst * t_src < 2**24:
                order = native_rt.sort_by_tile(
                    key.astype(np.int32, copy=False), t_dst * t_src
                )
            else:
                order = np.argsort(key, kind="stable")
            ks, ds = key[order], dst_of_edge[order]
            ss, ws = adj[order], weights[order]

            # (group, dst) runs -> packed rows of <= K slots each
            change = (ks[1:] != ks[:-1]) | (ds[1:] != ds[:-1])
            run_start = np.nonzero(np.concatenate([[True], change]))[0]
            run_len = np.diff(np.concatenate([run_start, [e_num]]))
            run_key, run_dst = ks[run_start], ds[run_start]
            rows_of_run = -(-run_len // K)
            n_rows = int(rows_of_run.sum())
            row_of_first = np.concatenate([[0], np.cumsum(rows_of_run)[:-1]])
            row_run = np.repeat(np.arange(len(run_start)), rows_of_run)
            row_key = run_key[row_run]
            row_dst = run_dst[row_run]

            # rows are key-sorted; rank within key -> (block, slot)
            key_change = np.nonzero(
                np.concatenate([[True], row_key[1:] != row_key[:-1]])
            )[0]
            first_row_of_key = np.repeat(
                key_change,
                np.diff(np.concatenate([key_change, [n_rows]])),
            )
            rank = np.arange(n_rows) - first_row_of_key
            # cumulative block count at each key group's start
            grp_rows = np.diff(np.concatenate([key_change, [n_rows]]))
            grp_blocks = -(-grp_rows // R)
            grp_block_start = np.concatenate([[0], np.cumsum(grp_blocks)[:-1]])
            blocks_before = np.repeat(grp_block_start, grp_rows)
            row_block = blocks_before + rank // R
            row_slot = rank % R
            n_data_blocks = int(grp_blocks.sum())
        else:
            n_rows = n_data_blocks = 0
            row_block = row_slot = row_dst = row_key = np.zeros(0, np.int64)

        # data blocks are created in key order, so bd is nondecreasing
        if n_data_blocks:
            blk_first = np.nonzero(
                np.concatenate([[True], row_block[1:] != row_block[:-1]])
            )[0]
            data_bd = (row_key[blk_first] // t_src).astype(np.int64)
            data_bs = (row_key[blk_first] % t_src).astype(np.int32)
        else:
            data_bd = np.zeros(0, np.int64)
            data_bs = np.zeros(0, np.int32)

        # fill the DATA blocks into dense temp tables (block ids are the
        # data block ids 0..n_data-1, exactly what row_block holds)
        nbr_d = np.zeros((n_data_blocks, K, R), dtype=np.int32)
        wgt_d = np.zeros((n_data_blocks, K, R), dtype=np.float32)
        ldst_d = np.zeros((n_data_blocks, R), dtype=np.int32)
        if e_num:
            src_local = (ss - (ss // vt) * vt).astype(np.int32)
            run_ldst = (run_dst - (run_dst // dt) * dt).astype(np.int32)
            if native_rt.available():
                # one OpenMP pass over runs (the three O(E) fancy-index
                # scatters below were the measured build bottleneck)
                native_rt.fill_bsp(
                    run_start, run_len, row_of_first, run_ldst,
                    row_block, row_slot, src_local,
                    np.ascontiguousarray(ws, np.float32), K, R,
                    nbr_d, wgt_d, ldst_d,
                )
            else:
                # per-edge placement: row-relative slot position
                run_of_edge = np.repeat(np.arange(len(run_start)), run_len)
                off = np.arange(e_num) - run_start[run_of_edge]
                e_row = row_of_first[run_of_edge] + off // K
                p = off % K
                b_e = row_block[e_row]
                s_e = row_slot[e_row]
                nbr_d[b_e, p, s_e] = src_local
                wgt_d[b_e, p, s_e] = ws
                ldst_d[row_block, row_slot] = run_ldst[row_run]

        # --- SMEM-budget segmentation (VERDICT r3 item 3) -----------------
        # Cut the grid into S contiguous dst-tile RANGES, each carrying at
        # most `max_blocks` blocks, so every pallas_call's [b_seg] key fits
        # SMEM. Ranges are packed greedily by BLOCK count (balanced: pad
        # blocks don't scale with cross-segment degree skew) under a
        # tile-count cap that bounds the per-call output buffer. When
        # segmented, b_seg is pinned to the budget and t_seg rounds up to
        # a 128-multiple so the compiled-program MENU is small and
        # provable by AOT (tools/aot_bsp_scale.py); per-block geometry —
        # the Mosaic lowering surface — is t_seg-invariant. A call's
        # output tiles beyond its real range are never written or read
        # (aggregate slices each call to its own range).
        # Within a segment: data blocks first (grouped per tile), then one
        # filler block per empty tile in range (every real tile must be
        # visited once so its output is zero-initialized — an unvisited
        # pallas output block would be uninitialized memory), then pad
        # blocks repeating the last real block's key (weight 0:
        # accumulate nothing, never re-zero). The kernel only needs each
        # tile's blocks CONSECUTIVE, which all three groups preserve.
        cap_eff = (max_blocks // 8) * 8
        blocks_per_tile = np.bincount(data_bd, minlength=t_dst).astype(np.int64)
        need = np.maximum(blocks_per_tile, 1)  # empty tiles need a filler
        if t_dst and int(need.max()) > cap_eff:
            raise ValueError(
                f"bsp ELL: a single dst tile needs {int(need.max())} blocks,"
                f" over the {max_blocks}-block SMEM key budget; raise dt/K/R"
                " or NTS_BSP_MAX_BLOCKS"
            )
        total_need = int(need.sum())
        s_est = max(1, -(-total_need // max(cap_eff, 1)))
        t_seg_cap = min(t_dst, 2 * (-(-t_dst // s_est))) if t_dst else 0
        # BALANCED packing: close a segment at ceil(total/S) blocks, not
        # at the cap — fill-to-cap left the LAST segment nearly empty and
        # the uniform b_seg then padded it with cap-sized dead work
        # (measured at full-scale vt=2048: 258k data blocks -> 458k padded
        # grid steps, 1.78x; balancing + the quantized b_seg below holds
        # that to ~1.1x). The cap stays the hard bound.
        target = min(cap_eff, -(-total_need // s_est))
        seg_of_tile = np.empty(t_dst, np.int64)
        first_tile = [0]
        acc_b = acc_t = seg = 0
        for tile in range(t_dst):  # t_dst ~ 4.5k at 10x Reddit: cheap
            nb = int(need[tile])
            if acc_t and (
                acc_t + 1 > t_seg_cap
                or acc_b + nb > target
            ):
                seg += 1
                first_tile.append(tile)
                acc_b = acc_t = 0
            seg_of_tile[tile] = seg
            acc_b += nb
            acc_t += 1
        # tail-merge the tile-granularity spill: closing at the balanced
        # target can strand a near-empty final segment (full-scale
        # vt=2048: a 0.4k-block 3rd segment that b_seg would pad with
        # 143k dead blocks); fold trailing segments back while the cap
        # and the tile bound both still hold
        seg_blocks = np.bincount(
            seg_of_tile, weights=need.astype(np.float64), minlength=seg + 1
        ).astype(np.int64)
        seg_tiles_n = np.bincount(seg_of_tile, minlength=seg + 1)
        while (
            len(first_tile) >= 2
            and seg_blocks[-1] + seg_blocks[-2] <= cap_eff
            and seg_tiles_n[-1] + seg_tiles_n[-2] <= t_seg_cap
        ):
            last = len(first_tile) - 1
            seg_of_tile[seg_of_tile == last] = last - 1
            seg_blocks[-2] += seg_blocks[-1]
            seg_tiles_n[-2] += seg_tiles_n[-1]
            seg_blocks = seg_blocks[:-1]
            seg_tiles_n = seg_tiles_n[:-1]
            first_tile.pop()
        S = len(first_tile)
        first_tile = np.asarray(first_tile, np.int64)
        tiles_in_seg = np.bincount(seg_of_tile, minlength=S)
        seg_of_data = seg_of_tile[data_bd] if n_data_blocks else data_bd
        counts_data = np.bincount(seg_of_data, minlength=S)
        empty_tiles = np.nonzero(blocks_per_tile == 0)[0]
        seg_of_fill = seg_of_tile[empty_tiles]
        counts_fill = np.bincount(seg_of_fill, minlength=S)
        used = counts_data + counts_fill
        if S == 1:
            t_seg = int(t_dst)
            b_seg = int(used.max()) if t_dst else 0
            b_seg += (-b_seg) % 8
        else:  # quantized: a small provable program menu (see above).
            # BOTH grid dims snap up to shared menus — b_seg to the
            # 8-value bsp_bseg_menu(cap), t_seg to the <=16-value
            # bsp_tseg_menu(t_dst) (trailing output tiles are never
            # written or read, so the snap costs only padded output
            # rows). tools/aot_bsp_scale compiles the exact
            # (b_seg menu) x (t_seg menu) lattice, so every program a
            # segmented build can emit is pre-lowered.
            tiles_max = int(tiles_in_seg.max())
            t_seg = next(v for v in bsp_tseg_menu(t_dst) if v >= tiles_max)
            u_max = int(used.max())
            b_seg = next(v for v in bsp_bseg_menu(cap_eff) if v >= u_max)
        assert b_seg <= max_blocks  # the construction's SMEM invariant

        B_total = S * b_seg
        nbr = np.zeros((B_total, K, R), dtype=np.int32)
        wgt = np.zeros((B_total, K, R), dtype=np.float32)
        ldst = np.zeros((B_total, R), dtype=np.int32)
        key = np.zeros(B_total, dtype=np.int32)
        if n_data_blocks:
            seg_first = np.concatenate([[0], np.cumsum(counts_data)[:-1]])
            pos = (
                seg_of_data * b_seg
                + np.arange(n_data_blocks)
                - seg_first[seg_of_data]
            )
            nbr[pos], wgt[pos], ldst[pos] = nbr_d, wgt_d, ldst_d
            key[pos] = (data_bd - first_tile[seg_of_data]) * t_src + data_bs
        if len(empty_tiles):
            fill_first = np.concatenate(
                [[0], np.cumsum(counts_fill)[:-1]]
            )
            key[
                seg_of_fill * b_seg
                + counts_data[seg_of_fill]
                + np.arange(len(empty_tiles))
                - fill_first[seg_of_fill]
            ] = (empty_tiles - first_tile[seg_of_fill]) * t_src
        if B_total:
            idx = np.arange(B_total)
            seg_idx = idx // b_seg
            pad_mask = (idx % b_seg) >= used[seg_idx]
            key[pad_mask] = key[
                (seg_idx * b_seg + used[seg_idx] - 1)[pad_mask]
            ]

        if e_num:
            waste = B_total * K * R / max(e_num, 1)
            log.info(
                "bsp ELL: %d blocks [%d slots x %d rows] in %d segment(s) "
                "of %d, %d dst x %d src tiles, %d packed rows, slot waste "
                "%.2fx",
                B_total, K, R, S, b_seg, t_dst, t_src, n_rows, waste,
            )
        conv = (lambda a: a) if keep_host else jnp.asarray
        return BspEll(
            nbr=conv(nbr),
            wgt=conv(wgt),
            ldst=conv(ldst),
            blk_key=conv(key),
            v_num=int(v_num),
            dt=int(dt),
            vt=int(vt),
            src_num=int(src_num),
            n_seg=int(S),
            b_seg=int(b_seg),
            t_seg=int(t_seg),
            seg_tiles=tuple(int(t) for t in tiles_in_seg),
        )

    def aggregate(self, x: jax.Array, interpret: bool = None) -> jax.Array:
        """out[v] = sum over in-edges of w * x[src]; [V, f] -> [V, f]."""
        if interpret is None:
            # topology AOT compiles must lower real Mosaic, not the
            # interpret emulation (round-3 near-miss: an AOT "verification"
            # of this kernel silently compiled the emulation)
            interpret = pallas_interpret_default()
        f = x.shape[1]
        n_src = self.src_num or self.v_num
        t_dst = -(-self.v_num // self.dt)
        t_src = -(-n_src // self.vt)
        B = self.nbr.shape[0]
        if B == 0 or f == 0:
            return jnp.zeros((self.v_num, f), x.dtype)
        xp = jnp.pad(x, ((0, t_src * self.vt - n_src), (0, 0)))
        # one pallas_call per SMEM-budget segment: identical shapes, so
        # ONE compiled program serves all n_seg calls (the program is
        # V-independent; only this Python loop grows with scale). Each
        # call's output is sliced to its segment's REAL tile range —
        # trailing output tiles (t_seg is quantized) are never read.
        t_seg = self.t_seg or t_dst
        b_seg = self.b_seg or B
        seg_tiles = self.seg_tiles or (t_dst,)
        outs = []
        for s in range(self.n_seg):
            sl = slice(s * b_seg, (s + 1) * b_seg)
            outs.append(
                _bsp_call(
                    self.blk_key[sl], self.nbr[sl], self.wgt[sl],
                    self.ldst[sl], xp,
                    dt=self.dt, vt=self.vt, t_dst=t_seg, t_src=t_src,
                    interpret=interpret,
                )[: seg_tiles[s] * self.dt]
            )
        out = outs[0] if self.n_seg == 1 else jnp.concatenate(outs, axis=0)
        return out[: self.v_num].astype(x.dtype)


def _bsp_kernel(key_ref, nbr_ref, wgt_ref, ldst_ref, x_ref, o_ref, *, dt, vt, t_src):
    """One block, gather-free BY CONSTRUCTION (Mosaic's only gather is an
    elementwise same-shape shuffle — a row gather cannot lower, docs/PERF.md
    section 5): the block's <=K*R edges are folded into a
    weights-valued one-hot matrix W [R, vt] (W[r, src_local] = w), so
    gather+scale+K-reduce is ONE bf16 MXU matmul ``W @ slab``; the row
    partial sums then land in the dst tile through the one-hot(ldst)
    scatter matmul (f32, dt*R*f — an order smaller than the main dot).
    The dst tile is zeroed on its first visit and accumulated in f32
    across its consecutive blocks."""
    b = pl.program_id(0)
    prev_dst = key_ref[jnp.maximum(b - 1, 0)] // t_src

    @pl.when(jnp.logical_or(b == 0, key_ref[b] // t_src != prev_dst))
    def _init():
        o_ref[:] = jnp.zeros_like(o_ref)

    x = x_ref[:]  # [vt, f]
    K, R = nbr_ref.shape[1], nbr_ref.shape[2]
    # The one-hot W build is the ONLY Mosaic-expressible gather form —
    # both direct alternatives were tried against the topology compiler
    # and die inside Mosaic (2026-07-31):
    # (a) pad the K*R slot indices to the slab height and use the legal
    #     same-shape take_along_axis: "Gather indices and result have
    #     different bitwidths" (i32 idx vs bf16 data), and with an f32
    #     view: "Not implemented: Multiple source vregs along gather
    #     dimension" — tpu.dynamic_gather only shuffles WITHIN one
    #     8-sublane vreg, so any cross-slab row fetch is out.
    # (b) a resident-table row gather (the kernel removed in PR 31) — same
    #     root cause.
    # Numeric policy: W entries round to the slab dtype (bf16 in
    # production) so the main dot runs at full MXU rate; accumulation is
    # f32 (preferred_element_type) in-block and across blocks. The build
    # costs O(K * R * vt) VPU compares per block — the lever that makes
    # SMALLER src tiles attractive (the plan's bsp_vt_* sweep).
    # The MXU rounds f32 operands to bf16 at the default precision (on the
    # v5e: 3.4e-3 relative against the f64 golden, tests/test_tpu.py, where
    # every XLA aggregation path is f32-exact). An f32 slab asks for f32
    # products; the bf16 production slab keeps the default, full-rate dot.
    precision = (
        lax.Precision.HIGHEST if x.dtype == jnp.float32 else None
    )
    col = lax.broadcasted_iota(jnp.int32, (R, vt), 1)
    w = jnp.zeros((R, vt), jnp.float32)
    for k in range(K):  # K is a small static constant: full unroll
        nb = nbr_ref[0, k, :]
        wb = wgt_ref[0, k, :]
        # srcs within one packed row are distinct, so += never collides
        w = w + jnp.where(col == nb[:, None], wb[:, None], 0.0)
    acc = lax.dot_general(
        w.astype(x.dtype), x, (((1,), (0,)), ((), ())),
        precision=precision, preferred_element_type=jnp.float32,
    )  # [R, f]
    # ldst rides in [8-row, R] VMEM blocks (Mosaic tiling needs sublane
    # multiples of 8); this block's row is a dynamic sublane select
    ld = ldst_ref[b % 8, :]  # [R]
    onehot = (
        lax.broadcasted_iota(jnp.int32, (dt, R), 0) == ld[None, :]
    ).astype(jnp.float32)
    o_ref[:] += lax.dot_general(
        onehot, acc, (((1,), (0,)), ((), ())),
        precision=precision, preferred_element_type=jnp.float32,
    )


@functools.partial(
    jax.jit, static_argnames=("dt", "vt", "t_dst", "t_src", "interpret")
)
def _bsp_call(blk_key, nbr, wgt, ldst, xp, *, dt, vt, t_dst, t_src, interpret):
    B, K, R = nbr.shape
    f = xp.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        # ONE packed (dst_tile, src_tile) key drives both index maps —
        # SMEM holds ~1 MB of scalars total (see BspEll.blk_key)
        num_scalar_prefetch=1,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, K, R), lambda b, key: (b, 0, 0)),
            pl.BlockSpec((1, K, R), lambda b, key: (b, 0, 0)),
            # ldst blocks are 8 sublanes tall (Mosaic tiling); the kernel
            # selects its row via b % 8. Build pads B to a multiple of 8.
            pl.BlockSpec((8, R), lambda b, key: (b // 8, 0)),
            pl.BlockSpec((vt, f), lambda b, key: (key[b] % t_src, 0)),
        ],
        out_specs=pl.BlockSpec((dt, f), lambda b, key: (key[b] // t_src, 0)),
    )
    return pl.pallas_call(
        functools.partial(_bsp_kernel, dt=dt, vt=vt, t_src=t_src),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t_dst * dt, f), jnp.float32),
        interpret=interpret,
    )(blk_key, nbr, wgt, ldst, xp)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class BspEllPair:
    """Forward (CSC) + backward (CSR) block tables, custom_vjp-paired."""

    fwd: BspEll
    bwd: BspEll

    @staticmethod
    def from_host(
        g: CSCGraph,
        dt: int = 0,
        vt: int = DEFAULT_VT,
        k_slots: int = 0,
        r_rows: int = DEFAULT_R,
    ) -> "BspEllPair":
        # dt (dst-tile height: the scatter matmul's cost axis) and K
        # (slots/row: trades rows-per-edge against per-row padding) are
        # env-tunable so on-chip A/Bs need no code edits:
        # NTS_BSP_DT / NTS_BSP_K
        dt, k_slots = resolve_bsp_knobs(dt, k_slots)
        fwd = BspEll.build(
            g.v_num, g.column_offset, g.row_indices, g.edge_weight_forward,
            dt, vt, k_slots, r_rows,
        )
        bwd = BspEll.build(
            g.v_num, g.row_offset, g.column_indices, g.edge_weight_backward,
            dt, vt, k_slots, r_rows,
        )
        return BspEllPair(fwd=fwd, bwd=bwd)

    def gather_dst_from_src(self, x: jax.Array) -> jax.Array:
        """Streamed block-sparse weighted aggregation (custom_vjp-paired)."""
        return _bsp_aggregate(self.fwd, self.bwd, x)

    def gather_src_from_dst(self, y: jax.Array) -> jax.Array:
        """The CSR direction as a forward op."""
        return _bsp_aggregate(self.bwd, self.fwd, y)

    def describe(self) -> str:
        return (
            f"streamed block-sparse Pallas aggregation "
            f"({self.fwd.nbr.shape[0]} fwd blocks, dt={self.fwd.dt} "
            f"vt={self.fwd.vt})"
        )


@jax.custom_vjp
def _bsp_aggregate(fwd: BspEll, bwd: BspEll, x: jax.Array):
    return fwd.aggregate(x)


def _bsp_aggregate_fwd(fwd, bwd, x):
    return fwd.aggregate(x), (fwd, bwd)


def _bsp_aggregate_bwd(res, g):
    from neutronstarlite_tpu.ops.segment import zero_cotangent

    fwd, bwd = res
    zero = jax.tree.map(zero_cotangent, (fwd, bwd))
    return (*zero, bwd.aggregate(g))


_bsp_aggregate.defvjp(_bsp_aggregate_fwd, _bsp_aggregate_bwd)


def bsp_gather_dst_from_src(pair: BspEllPair, x: jax.Array) -> jax.Array:
    return pair.gather_dst_from_src(x)


def bsp_gather_src_from_dst(pair: BspEllPair, y: jax.Array) -> jax.Array:
    return pair.gather_src_from_dst(y)
