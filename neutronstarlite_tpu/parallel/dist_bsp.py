"""Distributed Mosaic bsp aggregation: all_gather + per-shard block tables.

The fused-kernel story on the dist path, completed: `PALLAS:1` on a real
TPU mesh runs the SAME gather-free streamed block-sparse kernel the
single chip runs (ops/bsp_ell.py — weights-folded one-hot MXU gather,
one-hot scatter matmul, packed SMEM tile key), in its RECTANGULAR form:
each device's destination rows are its own vp vertices while the source
space is the full all_gathered [P*vp, f] slab. Because the kernel
STREAMS source slabs per tile from HBM, the gathered slab has no VMEM
bound — the dist regime that forced the blocked XLA layout's design
(parallel/dist_blocked.py) is native territory for this kernel.

Layout: per-device BspEll tables built from the same per-device global
adjacency the dist-ELL/blocked layouts use (parallel/dist_ell.py
``per_device_adjacency``), stacked [P, B, ...] with the cross-device max
block count (pad blocks carry weight 0 and the device's last tile key,
so the zero-init revisit logic is untouched). SPMD-uniform shapes, the
same "static shapes replace variable-length messages" move as the other
layers. Per-shard SMEM check: the [B] packed key at full Reddit scale
P=8 is ~20-30k blocks -> ~100 KB, far inside the 1 MB budget that the
single-chip table had to squeeze (ops/bsp_ell.py blk_key note).

Backward: custom_vjp pairs the transposed per-device tables (device rows
= its srcs, neighbors = global dst ids), exactly the dist-ELL pairing.
Reference analog: the distributed GPU engine dispatching the same CUDA
kernels as the single-GPU path (core/graph.hpp:3640 + cuda/
ntsCUDAFuseKernel.cuh:147) — here the same Mosaic kernel serves both.
"""

from __future__ import annotations

import dataclasses
from typing import List

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as PS

from neutronstarlite_tpu.ops.bsp_ell import (
    DEFAULT_R,
    DEFAULT_VT,
    BspEll,
    _bsp_call,
    pallas_interpret_default,
    resolve_bsp_knobs,
)
from neutronstarlite_tpu.parallel.dist_ell import per_device_adjacency
from neutronstarlite_tpu.parallel.dist_graph import DistGraph
from neutronstarlite_tpu.parallel.mesh import PARTITION_AXIS, shard_map
from neutronstarlite_tpu.utils.logging import get_logger

log = get_logger("dist_bsp")

# per-chunk VMEM-stack budget for the kernel OUTPUT under shard_map (the
# whole [t_dst*dt, fc] f32 chunk is stack-allocated there; ~36 MB leaves
# room for the double-buffered slab blocks and the W matrix)
_DIST_OUT_BUDGET_BYTES = 36 << 20


def bsp_call_width(t_call: int, dt: int, f: int) -> int:
    """The per-call slab width the VMEM-stack budget allows for a kernel
    call covering ``t_call`` dst tiles: f itself when it fits, else the
    balanced 128-multiple chunk width (ceil-divide f into equal chunks
    instead of full-budget chunks + a mostly-padding tail). ONE definition
    shared by DistBsp._local_aggregate (the runtime chunking) and
    tools/aot_bsp_scale (the compiled-program proof) — a drifted copy
    would make the AOT tool seed programs at the wrong slab width
    (r5 review)."""
    fc_max = max(
        _DIST_OUT_BUDGET_BYTES // (t_call * dt * 4) // 128 * 128, 128
    )
    if f <= fc_max:
        return f
    n_ch = -(-f // fc_max)
    per_ch = -(-f // n_ch)
    return -(-per_ch // 128) * 128


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class DistBsp:
    """One direction's stacked per-device rectangular bsp tables.

    Segmented form (round 5, VERDICT r4 item 6): when any shard's block
    count exceeds the SMEM key budget, EVERY shard is re-laid to a uniform
    (n_seg, b_seg, t_seg) geometry — b_seg/t_seg snapped to the shared AOT
    menus (ops/bsp_ell.bsp_bseg_menu / bsp_tseg_menu) — because shard_map
    traces ONE program for all shards. ``first_tile[p, s]`` carries each
    shard's per-segment output placement as DATA (a traced int array, the
    only per-shard-varying piece): segment outputs are placed with ordered
    dynamic_update_slice, and a later segment's slice exactly overwrites
    the quantized tail rows (t_seg snap) of the previous one, so no
    masking is needed; the final segment's tail lands in a scratch margin.
    Dummy segments (shards with fewer real segments) place at t_dst — the
    scratch start — and cover zero real tiles."""

    nbr: jax.Array  # [P, S*b_seg, K, R] int32 tile-local src ids
    wgt: jax.Array  # [P, S*b_seg, K, R] f32 (0 on padding)
    ldst: jax.Array  # [P, S*b_seg, R] int32 tile-local dst row
    blk_key: jax.Array  # [P, S*b_seg] int32 packed segment-LOCAL (dst,src)
    first_tile: jax.Array  # [P, S] int32 segment -> first dst tile (t_dst
    #                         = scratch placement for dummy segments)
    partitions: int = dataclasses.field(metadata=dict(static=True))
    vp: int = dataclasses.field(metadata=dict(static=True))
    dt: int = dataclasses.field(metadata=dict(static=True))
    vt: int = dataclasses.field(metadata=dict(static=True))
    n_seg: int = dataclasses.field(default=1, metadata=dict(static=True))
    b_seg: int = dataclasses.field(default=0, metadata=dict(static=True))
    t_seg: int = dataclasses.field(default=0, metadata=dict(static=True))

    @staticmethod
    def build(
        dist: DistGraph,
        transpose: bool,
        dt: int = 0,  # 0 -> NTS_BSP_DT env / DEFAULT_DT (same knobs as
        vt: int = DEFAULT_VT,  # the single-chip BspEllPair.from_host)
        k_slots: int = 0,
        r_rows: int = DEFAULT_R,
    ) -> "DistBsp":
        from neutronstarlite_tpu.ops.bsp_ell import (
            bsp_bseg_menu,
            bsp_tseg_menu,
        )

        dt, k_slots = resolve_bsp_knobs(dt, k_slots)
        P, vp = dist.partitions, dist.vp
        t_dst = -(-vp // dt)
        per_dev, _ = per_device_adjacency(dist, transpose)
        tables: List[BspEll] = [
            BspEll.build(
                vp, offs, nbr_g, w, dt=dt, vt=vt, k_slots=k_slots,
                r_rows=r_rows, src_num=P * vp,
                # tables stay numpy: both stacked layouts below re-lay or
                # pad them host-side and stack them on the host; shard()
                # sends each device its own slice (a jnp stack would land
                # every shard's tables whole on device 0 first)
                keep_host=True,
            )
            for offs, nbr_g, w, _deg in per_dev
        ]
        S_max = max(t.n_seg for t in tables)
        if S_max == 1:
            # fast path: the pre-round-5 stacked single-segment layout
            # (global keys, one call per shard, no placement arithmetic)
            b_max = max(t.nbr.shape[0] for t in tables)
            # pad to a multiple of 8 ACROSS devices too (the kernel's
            # 8-row ldst blocks index by global block id)
            b_max += (-b_max) % 8

            def pad(t: BspEll):
                pad_b = b_max - t.nbr.shape[0]
                if pad_b == 0:
                    return t.nbr, t.wgt, t.ldst, t.blk_key
                k, r = t.nbr.shape[1], t.nbr.shape[2]
                return (
                    np.concatenate(
                        [t.nbr, np.zeros((pad_b, k, r), np.int32)]
                    ),
                    np.concatenate(
                        [t.wgt, np.zeros((pad_b, k, r), np.float32)]
                    ),
                    np.concatenate(
                        [t.ldst, np.zeros((pad_b, r), np.int32)]
                    ),
                    # the device's LAST key: extends that tile's
                    # consecutive run (the kernel's ordering invariant —
                    # tables are data-then-filler grouped, NOT tile-
                    # sorted) and the pad blocks never re-zero a tile
                    # (weight-0 accumulate)
                    np.concatenate(
                        [t.blk_key, np.full(pad_b, t.blk_key[-1], np.int32)]
                    ),
                )

            padded = [pad(t) for t in tables]
            return DistBsp(
                nbr=np.stack([p[0] for p in padded]),
                wgt=np.stack([p[1] for p in padded]),
                ldst=np.stack([p[2] for p in padded]),
                blk_key=np.stack([p[3] for p in padded]),
                first_tile=np.zeros((P, 1), np.int32),
                partitions=P, vp=vp, dt=int(dt), vt=int(vt),
                n_seg=1, b_seg=0, t_seg=0,
            )

        # ---- segmented: re-lay every shard to uniform menu geometry ------
        from neutronstarlite_tpu.ops.bsp_ell import DEFAULT_MAX_BLOCKS
        import os as _os

        cap = int(_os.environ.get("NTS_BSP_MAX_BLOCKS", DEFAULT_MAX_BLOCKS))
        menu_b = bsp_bseg_menu((cap // 8) * 8)
        need_b = max(
            (t.b_seg or (-(-t.nbr.shape[0] // 8) * 8)) for t in tables
        )
        b_seg_u = next(v for v in menu_b if v >= need_b)
        menu_t = bsp_tseg_menu(t_dst)
        need_t = max(
            max(t.seg_tiles) if t.seg_tiles else t_dst for t in tables
        )
        t_seg_u = next(v for v in menu_t if v >= need_t)

        def relay(t: BspEll):
            """[S_p * b_seg_p] arrays -> [S_max * b_seg_u] + first_tile."""
            S_p = t.n_seg
            b_p = t.b_seg or t.nbr.shape[0]
            K, R = t.nbr.shape[1], t.nbr.shape[2]
            nbr = np.zeros((S_max, b_seg_u, K, R), np.int32)
            wgt = np.zeros((S_max, b_seg_u, K, R), np.float32)
            ldst = np.zeros((S_max, b_seg_u, R), np.int32)
            key = np.zeros((S_max, b_seg_u), np.int32)
            src_n = np.asarray(t.nbr).reshape(S_p, b_p, K, R)
            src_w = np.asarray(t.wgt).reshape(S_p, b_p, K, R)
            src_l = np.asarray(t.ldst).reshape(S_p, b_p, R)
            src_k = np.asarray(t.blk_key).reshape(S_p, b_p)
            nbr[:S_p, :b_p] = src_n
            wgt[:S_p, :b_p] = src_w
            ldst[:S_p, :b_p] = src_l
            key[:S_p, :b_p] = src_k
            # in-segment pad: repeat each segment's last key (weight 0 -
            # accumulate nothing, never re-zero); the source rows are
            # already pad-terminated so src_k[:, -1] is each segment's
            # last real tile's key
            key[:S_p, b_p:] = src_k[:, -1:]
            # dummy segments keep key 0 / weight 0: their single visited
            # tile zero-inits locally and the output is placed at the
            # scratch margin (first_tile = t_dst), never read
            seg_tiles = list(t.seg_tiles) if t.seg_tiles else [t_dst]
            first = np.full(S_max, t_dst, np.int32)
            first[:S_p] = np.concatenate(
                [[0], np.cumsum(seg_tiles[:-1], dtype=np.int64)]
            ).astype(np.int32)
            return (
                nbr.reshape(S_max * b_seg_u, K, R),
                wgt.reshape(S_max * b_seg_u, K, R),
                ldst.reshape(S_max * b_seg_u, R),
                key.reshape(S_max * b_seg_u),
                first,
            )

        relaid = [relay(t) for t in tables]
        total_blocks = P * S_max * b_seg_u
        real_blocks = sum(t.nbr.shape[0] for t in tables)
        log.info(
            "dist-bsp: segmented stacked layout %d shard(s) x %d segment(s)"
            " x %d blocks (t_seg %d, %.2fx stack pad over %d per-shard "
            "padded blocks; per-shard slot waste is logged by each "
            "BspEll.build line above)",
            P, S_max, b_seg_u, t_seg_u,
            total_blocks / max(real_blocks, 1), real_blocks,
        )
        return DistBsp(
            nbr=np.stack([r[0] for r in relaid]),
            wgt=np.stack([r[1] for r in relaid]),
            ldst=np.stack([r[2] for r in relaid]),
            blk_key=np.stack([r[3] for r in relaid]),
            first_tile=np.stack([r[4] for r in relaid]),
            partitions=P, vp=vp, dt=int(dt), vt=int(vt),
            n_seg=int(S_max), b_seg=int(b_seg_u), t_seg=int(t_seg_u),
        )

    def slot_count(self) -> int:
        import math

        return int(math.prod(self.nbr.shape))

    def shard(self, mesh: Mesh) -> "DistBsp":
        from jax.sharding import NamedSharding

        def put(a):
            spec = PS(PARTITION_AXIS, *([None] * (a.ndim - 1)))
            return jax.device_put(a, NamedSharding(mesh, spec))

        return DistBsp(
            nbr=put(self.nbr), wgt=put(self.wgt), ldst=put(self.ldst),
            blk_key=put(self.blk_key), first_tile=put(self.first_tile),
            partitions=self.partitions,
            vp=self.vp, dt=self.dt, vt=self.vt,
            n_seg=self.n_seg, b_seg=self.b_seg, t_seg=self.t_seg,
        )

    # -- per-device body (collective-free given the gathered slab) ---------
    def _local_aggregate(self, tables, xg: jax.Array) -> jax.Array:
        nbr, wgt, ldst, key, first_tile = tables
        n_src = self.partitions * self.vp
        f = xg.shape[1]
        t_dst = -(-self.vp // self.dt)
        t_src = -(-n_src // self.vt)
        xp = jnp.pad(xg, ((0, t_src * self.vt - n_src), (0, 0)))
        S = self.n_seg
        t_call = self.t_seg if S > 1 else t_dst
        b_seg = self.b_seg if S > 1 else key.shape[0]

        def call(xc):
            if S == 1:
                return _bsp_call(
                    key, nbr, wgt, ldst, xc,
                    dt=self.dt, vt=self.vt, t_dst=t_dst, t_src=t_src,
                    interpret=pallas_interpret_default(),
                )[: self.vp]
            # segmented: one identical-shape call per segment; outputs are
            # placed by ordered dynamic_update_slice at first_tile[s]*dt.
            # Segment s's quantized tail rows (t_seg snap-up, never written
            # by the kernel) are exactly overwritten by segment s+1's
            # placement (contiguous tile coverage), and the LAST segment's
            # tail lands in the scratch margin below — so no masking.
            buf = jnp.zeros(
                (t_dst * self.dt + t_call * self.dt, xc.shape[1]), jnp.float32
            )
            for s in range(S):
                sl = slice(s * b_seg, (s + 1) * b_seg)
                seg = _bsp_call(
                    key[sl], nbr[sl], wgt[sl], ldst[sl], xc,
                    dt=self.dt, vt=self.vt, t_dst=t_call, t_src=t_src,
                    interpret=pallas_interpret_default(),
                )
                buf = lax.dynamic_update_slice(
                    buf, seg, (first_tile[s] * self.dt, 0)
                )
            return buf[: self.vp]

        # Under shard_map XLA:TPU stack-allocates the custom call's WHOLE
        # output in VMEM (observed 2026-07-31: RESOURCE_EXHAUSTED at a
        # 38 MB f32 [15872, 602] output that plain jit handles fine up to
        # at least 140 MB). Feature-chunk the call so each chunk's
        # [t_dst*dt, fc] f32 output fits the stack budget — columns are
        # independent, so this is numerically free; the eager-order
        # widths (128/41) stay single-chunk, the 602-wide standard-order
        # exchange pays ~fc-fold table re-reads exactly like the resident
        # design's f-chunking would have.
        if t_call * self.dt * 4 * 128 > _DIST_OUT_BUDGET_BYTES:
            # 128 lanes is the floor; past ~73k padded dst rows per call
            # even one chunk exceeds the stack budget — warn loudly, the
            # compile error alone would not say why
            log.warning(
                "dist-bsp: per-call output %d rows x 128 cols exceeds the "
                "%d MiB VMEM-stack budget; shard_map compile may "
                "RESOURCE_EXHAUST (raise PARTITIONS or lower dt)",
                t_call * self.dt, _DIST_OUT_BUDGET_BYTES >> 20,
            )
        # balanced 128-multiple chunk width under the per-call budget
        # (f=602 under a 512 budget: 2x384 beats 512+512-with-422-zeros);
        # ONE shared definition with the AOT proof tool (bsp_call_width)
        fc = bsp_call_width(t_call, self.dt, f)
        if f <= fc:
            return call(xp).astype(xg.dtype)
        n_ch = -(-f // fc)
        fpad = n_ch * fc - f
        if fpad:
            xp = jnp.pad(xp, ((0, 0), (0, fpad)))
        return jnp.concatenate(
            [call(xp[:, lo: lo + fc]) for lo in range(0, n_ch * fc, fc)],
            axis=1,
        )[:, :f].astype(xg.dtype)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class DistBspPair:
    """Forward + transposed tables; ``shard(mesh)`` before use."""

    fwd: DistBsp
    bwd: DistBsp

    @staticmethod
    def build(dist: DistGraph, vt: int = DEFAULT_VT) -> "DistBspPair":
        return DistBspPair(
            fwd=DistBsp.build(dist, transpose=False, vt=vt),
            bwd=DistBsp.build(dist, transpose=True, vt=vt),
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

    def shard(self, mesh: Mesh) -> "DistBspPair":
        return DistBspPair(fwd=self.fwd.shard(mesh), bwd=self.bwd.shard(mesh))

    def exchange(self, mesh: Mesh, x: jax.Array, wire_dtype=None,
                 partitioner=None) -> jax.Array:
        return dist_bsp_gather_dst_from_src(mesh, self, x)

    def describe(self) -> str:
        _, b, k, r = self.fwd.nbr.shape
        return (
            f"dist bsp aggregation (all_gather + [P, {b}, {k}, {r}] "
            f"stacked blocks, vt={self.fwd.vt})"
        )


def _dist_bsp_apply(mesh: Mesh, dbsp: DistBsp, x: jax.Array) -> jax.Array:
    """all_gather + per-shard rectangular bsp kernel, as a shard_map."""

    def body(nbr, wgt, ldst, key, first, xs):
        xg = lax.all_gather(xs, PARTITION_AXIS, axis=0, tiled=True)
        return dbsp._local_aggregate(
            (nbr[0], wgt[0], ldst[0], key[0], first[0]), xg
        )

    fn = shard_map(
        body,
        mesh=mesh,
        in_specs=(
            PS(PARTITION_AXIS, None, None, None),
            PS(PARTITION_AXIS, None, None, None),
            PS(PARTITION_AXIS, None, None),
            PS(PARTITION_AXIS, None),
            PS(PARTITION_AXIS, None),
            PS(PARTITION_AXIS, None),
        ),
        out_specs=PS(PARTITION_AXIS, None),
        # pallas_call cannot declare varying mesh axes on its out_shape
        # (same constraint as the dist-ELL pallas executor)
        check_vma=False,
    )
    return fn(dbsp.nbr, dbsp.wgt, dbsp.ldst, dbsp.blk_key, dbsp.first_tile, x)


def dist_bsp_gather_dst_from_src(
    mesh: Mesh, pair: DistBspPair, x: jax.Array
) -> jax.Array:
    """[P*vp, f] vertex-sharded -> aggregated [P*vp, f]; the custom_vjp
    backward runs the transposed tables (no autodiff through the kernel)."""

    @jax.custom_vjp
    def apply(x):
        return _dist_bsp_apply(mesh, pair.fwd, x)

    def apply_fwd(x):
        return apply(x), None

    def apply_bwd(_, g):
        return (_dist_bsp_apply(mesh, pair.bwd, g),)

    apply.defvjp(apply_fwd, apply_bwd)
    return apply(x)


def dist_bsp_gather_simulated(dbsp: DistBsp, x: jax.Array) -> jax.Array:
    """Collective-free twin (NTS_DIST_SIMULATE): per-device aggregation
    over the full x (the all_gather is the identity on one logical array)."""
    outs = []
    for p in range(dbsp.partitions):
        outs.append(
            dbsp._local_aggregate(
                (
                    dbsp.nbr[p], dbsp.wgt[p], dbsp.ldst[p],
                    dbsp.blk_key[p], dbsp.first_tile[p],
                ),
                x,
            )
        )
    return jnp.concatenate(outs, axis=0)
