"""Distributed edge-op family over the mirror-slot exchange.

TPU-native counterparts of the reference's Dist* operator chain
(core/ntsDistCPUGraphOp.hpp, GPU twins in core/ntsDistGPUGraphOp.hpp):

- ``dist_get_dep_nbr``      <- DistGetDepNbrOp (:34): materialize the mirror
  tensor from remote masters. Here: every producer gathers the rows its
  consumers asked for (MirrorGraph.need_ids) and ONE ``all_to_all`` delivers
  them — the whole P-step MPI ring of get_from_dep_neighbor_mutisockets
  (graph.hpp:2801) collapses into a single XLA collective whose backward
  (scatter-add + reverse all_to_all) is generated by autodiff exactly as the
  reference hand-writes its mirror->master gradient exchange (:85-124).
- ``dist_scatter_src``      <- DistScatterSrc (:127): mirror-V -> E gather
  through the slot table (the reference's ``MirrorIndex``-driven scatter).
- ``dist_scatter_dst``      <- DistScatterDst (:186): master-V -> E.
- ``dist_edge_softmax``     <- DistEdgeSoftMax (:442): per-dst softmax over
  the local (dst-sorted) edge list; reuses the fused-Jacobian custom_vjp of
  ops/edge.py.
- ``dist_aggregate_dst``    <- DistAggregateDst (:243): E -> master-V sum.
- ``dist_aggregate_dst_min`` / ``dist_aggregate_dst_max`` <- DistAggregateDst
  Min/Max (:306/:374): elementwise extreme over in-edges whose backward
  routes the gradient to the winning edge (the reference's ``record``
  argmin/argmax array, via nts_assign).
- ``dist_aggregate_dst_fuse_weight`` <- DistAggregateDstFuseWeight (:499):
  two-input op out[dst] = sum_e w_e * mirror[src_slot(e)]; autodiff yields
  both the feature gradient and the edge-weight gradient (the reference's
  get_additional_grad path, :581-590).
- ``dist_gather_dst_from_src_mirror``: fused GCN-style weighted aggregation
  through the mirror exchange — the compacted-communication alternative to
  the dense ppermute ring (dist_ops.py), shipping P*Mb mirror rows instead of
  P*vp shard rows per device per layer.

Every op is a ``shard_map`` over the 1-D partition mesh whose per-device body
is also exposed for the single-core test rig: the bodies are collective-free
except ``all_to_all``, so ``*_sim`` variants replace just the exchange with
its numpy-semantics equivalent and ``jax.vmap`` over the p axis (see
tests/test_dist_edge_ops.py), keeping test math bit-identical to the sharded
path.

Global array layouts (all leading axes sharded over the mesh axis ``p``):
  vertex tensors  [P*vp, f]   (padded vertex space, as dist_graph.py)
  mirror tensors  [P, P*Mb, f]
  edge tensors    [P, El, f]
"""

from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as PS

from neutronstarlite_tpu.ops.edge import _edge_extreme, _edge_softmax
from neutronstarlite_tpu.ops.segment import segment_sum_sorted
from neutronstarlite_tpu.parallel.mesh import (
    PARTITION_AXIS,
    pcast_varying,
    shard_map,
)
from neutronstarlite_tpu.parallel.mirror import MirrorGraph


def _shmap(body, mesh: Mesh, in_specs, out_specs):
    return shard_map(body, mesh=mesh, in_specs=in_specs, out_specs=out_specs)


# ---------------------------------------------------------------------------
# mirror materialization (DistGetDepNbrOp)
# ---------------------------------------------------------------------------

def _gather_rows(need, xs):
    """Producer-side gather: [P, Mb] ids into this device's [vp, f] shard."""
    return xs[need]  # [P, Mb, f]


def dist_get_dep_nbr(mesh: Mesh, mg: MirrorGraph, tables, x: jax.Array) -> jax.Array:
    """[P*vp, f] -> mirror tensor [P, P*Mb, f] via one all_to_all."""
    need_ids = tables[0]
    P, mb = mg.partitions, mg.mb

    def body(need, xs):  # need [1, P, Mb], xs [vp, f]
        rows = _gather_rows(need[0], xs)  # [P, Mb, f]
        got = lax.all_to_all(rows, PARTITION_AXIS, 0, 0, tiled=True)
        return got.reshape(1, P * mb, xs.shape[1])

    fn = _shmap(
        body,
        mesh,
        in_specs=(PS(PARTITION_AXIS, None, None), PS(PARTITION_AXIS, None)),
        out_specs=PS(PARTITION_AXIS, None, None),
    )
    return fn(need_ids, x)


def dist_get_dep_nbr_sim(mg: MirrorGraph, x: jax.Array) -> jax.Array:
    """Collective-free semantics of dist_get_dep_nbr (single-core test rig)."""
    P, mb, vp = mg.partitions, mg.mb, mg.vp
    xs = x.reshape(P, vp, -1)
    # rows[q, p] = x shard q gathered with need_ids[q, p]
    rows = jax.vmap(_gather_rows)(jnp.asarray(mg.need_ids), xs)  # [P, P, Mb, f]
    # after all_to_all, consumer p holds rows[:, p]
    return jnp.swapaxes(rows, 0, 1).reshape(P, P * mb, -1)


# ---------------------------------------------------------------------------
# local (collective-free) edge-op bodies
# ---------------------------------------------------------------------------

def _scatter_src_body(slot, mask, m):
    return m[slot] * mask[:, None].astype(m.dtype)


def _scatter_dst_body(dst, mask, xs):
    return xs[dst] * mask[:, None].astype(xs.dtype)


def _aggregate_dst_body(vp, dst, mask, ev):
    return segment_sum_sorted(ev * mask[:, None].astype(ev.dtype), dst, vp)


def _fuse_weight_body(vp, slot, dst, mask, w, m):
    if w.ndim == 1:
        w = w[:, None]
    vals = m[slot] * w * mask[:, None].astype(m.dtype)
    # accumulate WIDE regardless of the exchange dtype (bf16 inputs under
    # PRECISION:bfloat16): high-degree dsts sum hundreds of edge terms —
    # same policy as the chunked chain (r5 review finding)
    return segment_sum_sorted(vals.astype(jnp.float32), dst, vp)


def _edge_softmax_body(vp, dst, mask, score):
    return _edge_softmax(vp, dst, mask, score)


def _extreme_body(vp, is_min, dst, mask, ev):
    # shared masked-extreme + winning-edge-record custom_vjp (ops/edge.py)
    return _edge_extreme(vp, is_min, dst, mask, ev)


# ---------------------------------------------------------------------------
# shard_map wrappers
# ---------------------------------------------------------------------------

_E3 = PS(PARTITION_AXIS, None, None)
_E2 = PS(PARTITION_AXIS, None)


def dist_scatter_src(mesh, mg: MirrorGraph, tables, mirrors: jax.Array) -> jax.Array:
    """mirror [P, P*Mb, f] -> edge [P, El, f] through the slot table."""
    _, slot, _, _, mask = tables

    def body(s, mk, m):
        return _scatter_src_body(s[0], mk[0], m[0])[None]

    return _shmap(body, mesh, (_E2, _E2, _E3), _E3)(slot, mask, mirrors)


def dist_scatter_dst(mesh, mg: MirrorGraph, tables, x: jax.Array) -> jax.Array:
    """vertex [P*vp, f] -> edge [P, El, f] (dst side is always local)."""
    _, _, dst, _, mask = tables

    def body(d, mk, xs):
        return _scatter_dst_body(d[0], mk[0], xs)[None]

    return _shmap(body, mesh, (_E2, _E2, _E2), _E3)(dst, mask, x)


def dist_edge_softmax(mesh, mg: MirrorGraph, tables, score: jax.Array) -> jax.Array:
    """edge [P, El, h] -> per-(device, local dst) softmax, padding masked."""
    _, _, dst, _, mask = tables
    vp = mg.vp

    def body(d, mk, s):
        return _edge_softmax_body(vp, d[0], mk[0], s[0])[None]

    return _shmap(body, mesh, (_E2, _E2, _E3), _E3)(dst, mask, score)


def dist_aggregate_dst(mesh, mg: MirrorGraph, tables, edge_vals: jax.Array) -> jax.Array:
    """edge [P, El, f] -> vertex [P*vp, f] sum over in-edges."""
    _, _, dst, _, mask = tables
    vp = mg.vp

    def body(d, mk, ev):
        return _aggregate_dst_body(vp, d[0], mk[0], ev[0])

    return _shmap(body, mesh, (_E2, _E2, _E3), _E2)(dst, mask, edge_vals)


def dist_aggregate_dst_max(mesh, mg: MirrorGraph, tables, edge_vals: jax.Array) -> jax.Array:
    """edge [P, El, f] -> vertex [P*vp, f] per-dst elementwise max; backward
    routes the gradient to the winning edge (DistAggregateDstMax :374)."""
    _, _, dst, _, mask = tables
    vp = mg.vp

    def body(d, mk, ev):
        return _extreme_body(vp, False, d[0], mk[0], ev[0])

    return _shmap(body, mesh, (_E2, _E2, _E3), _E2)(dst, mask, edge_vals)


def dist_aggregate_dst_min(mesh, mg: MirrorGraph, tables, edge_vals: jax.Array) -> jax.Array:
    """Per-dst elementwise min (DistAggregateDstMin :306)."""
    _, _, dst, _, mask = tables
    vp = mg.vp

    def body(d, mk, ev):
        return _extreme_body(vp, True, d[0], mk[0], ev[0])

    return _shmap(body, mesh, (_E2, _E2, _E3), _E2)(dst, mask, edge_vals)


def dist_aggregate_dst_fuse_weight(
    mesh, mg: MirrorGraph, tables, edge_weight: jax.Array, mirrors: jax.Array
) -> jax.Array:
    """Two-input op: out[dst] = sum_e w_e * mirror[slot(e)] -> [P*vp, f]."""
    _, slot, dst, _, mask = tables
    vp = mg.vp

    def body(s, d, mk, w, m):
        return _fuse_weight_body(vp, s[0], d[0], mk[0], w[0], m[0])

    w_spec = _E3 if edge_weight.ndim == 3 else _E2
    return _shmap(body, mesh, (_E2, _E2, _E2, w_spec, _E3), _E2)(
        slot, dst, mask, edge_weight, mirrors
    )


def dist_gather_dst_from_src_mirror(
    mesh, mg: MirrorGraph, tables, x: jax.Array
) -> jax.Array:
    """Fused weighted aggregation out[v] = sum_in-edges w*x[src] through the
    compacted mirror exchange — ForwardCPUfuseOp semantics
    (ntsCPUFusedGraphOp.hpp:41) at mirror-set communication cost."""
    weight = tables[3]
    mirrors = dist_get_dep_nbr(mesh, mg, tables, x)
    return dist_aggregate_dst_fuse_weight(mesh, mg, tables, weight, mirrors)


# ---------------------------------------------------------------------------
# chunked + remat'd gated edge chain (round 5): the GAT/GGCN full-scale fit
# ---------------------------------------------------------------------------

def dist_gated_chain_chunked(
    mesh, mg: MirrorGraph, tables, payload: jax.Array, dst_half: jax.Array,
    f: int, slope: float
) -> jax.Array:
    """The gated edge chain (mirror fetch -> leaky(src_half + dst_half)
    score -> per-dst softmax -> gated aggregate of the payload's first f
    columns) executed CHUNK-AT-A-TIME over the dst-aligned edge chunking
    (parallel/mirror.chunk_edge_list) with per-chunk rematerialization.
    Serves BOTH edge-chain models: GAT (payload [h, al], width-1 score)
    and GGCN (payload [h, Ws.h], width-f' score) — the chain shape is
    identical, only the score width and leaky slope differ.

    Why: un-chunked, the chain holds ~6 live [El, *] edge tensors — AOT-
    measured 76.9 GiB (GGCN) / 14.8 GiB (GAT) of HBM temp at full Reddit
    against a 15.75 GiB chip (docs/perf_runs/round5/aot_fullscale.log).
    lax.scan over chunks bounds liveness to ONE chunk by construction
    (the unrolled form left XLA scheduling freedom that kept ~3 chunks
    resident: 22.1 GiB), and jax.checkpoint recomputes each chunk in the
    backward. Dst-aligned cuts keep every per-dst softmax segment whole;
    chunk outputs are placed by ordered dynamic_update_slice (the
    dist-bsp segment invariant: a later chunk's window exactly overwrites
    the zero rows past the previous chunk's real range, gaps stay zero
    because empty dsts aggregate to zero, and the final window's tail
    lands in a dp-row scratch margin).

    ``tables`` is the 7-tuple (need_ids, slot, dstl, dstr, mask, base,
    dp_probe): the chunked chain reads the mirror tensor by chunk slots,
    so the uniform [P, El] per-edge tables are never device-put (at full
    Reddit they were ~234 MB of dead HBM per device — r5 review)."""
    need, (cslot, cdstl, cdstr, cmask, cbase, dp_probe) = (
        tables[0], tables[1:]
    )
    vp = mg.vp
    mir = dist_get_dep_nbr(mesh, mg, (need,), payload)  # [P, P*Mb, f+s]

    def body(cs, cl, cr, cm, cb, dpp, mirrors, hds):
        mirrors = mirrors[0]  # [P*Mb, f+s]
        dp = dpp.shape[1]  # static chunk dst capacity (probe-shape carrier)

        def step(out, ck):
            s_k, l_k, r_k, m_k, b_k = ck

            def compute(mirrors, hds):
                rows = mirrors[s_k]  # [Ec, f+s]
                e_h, e_src = rows[:, :f], rows[:, f:]
                e_dst = hds[l_k]
                score = jax.nn.leaky_relu(
                    e_src + e_dst, negative_slope=slope
                )
                a = _edge_softmax_body(dp, r_k, m_k, score)
                vals = e_h * a * m_k[:, None].astype(e_h.dtype)
                # accumulate WIDE under bf16 compute (same policy as the
                # GCN family's slab aggregation): the gathers/softmax run
                # in the exchange dtype, the per-dst sum in f32
                return segment_sum_sorted(
                    vals.astype(jnp.float32), r_k, dp
                )

            seg = jax.checkpoint(compute)(mirrors, hds)
            return lax.dynamic_update_slice(out, seg, (b_k, 0)), None

        out0 = jnp.zeros((vp + dp, f), jnp.float32)
        # the body's output is device-varying (it reads sharded tables);
        # the zeros carry starts unvarying — pcast so scan's carry types
        # match (shard_map VMA rule; identity on pre-VMA runtimes)
        out0 = pcast_varying(out0, (PARTITION_AXIS,))
        out, _ = lax.scan(step, out0, (cs[0], cl[0], cr[0], cm[0], cb[0]))
        return out[:vp]

    _T3 = PS(PARTITION_AXIS, None, None)
    fn = _shmap(
        body,
        mesh,
        in_specs=(_T3, _T3, _T3, _T3, _E2, _E2, _T3, _E2),
        out_specs=_E2,
    )
    return fn(cslot, cdstl, cdstr, cmask, cbase, dp_probe, mir, dst_half)


# ---------------------------------------------------------------------------
# split-mirror fused aggregation (round 5): remote slots + resident locals
# ---------------------------------------------------------------------------

def _split_aggregate_body(
    vp, r_slot, r_dst, r_mask, r_w, l_src, l_dst, l_mask, l_w, mirrors, xs
):
    """out[dst] = sum_remote w*mirror[slot] + sum_local w*xs[src]; both edge
    lists are dst-sorted so the sorted segment sum serves each."""
    # accumulate WIDE even when the exchange ships bf16 rows (f32 weights
    # promote the products; the explicit upcast guards a future bf16-
    # weight layout) — the exchange dtype halves the WIRE, never the
    # accumulator
    rem = segment_sum_sorted(
        (mirrors[r_slot] * (r_w * r_mask)[:, None]).astype(jnp.float32),
        r_dst, vp,
    )
    loc = segment_sum_sorted(
        (xs[l_src] * (l_w * l_mask)[:, None]).astype(jnp.float32), l_dst, vp
    )
    return (rem + loc).astype(xs.dtype)


def dist_gather_dst_from_src_mirror_split(
    mesh, sm, tables, x: jax.Array
) -> jax.Array:
    """Fused weighted aggregation through the SPLIT mirror exchange
    (parallel/mirror.SplitMirror): the all_to_all ships only the
    off-diagonal need-sets ([P, mb_remote, f] per producer; the self-shard
    block is dead rows that never cross ICI), while local-src edges read
    the resident feature shard directly. Same semantics as
    dist_gather_dst_from_src_mirror at strictly-smaller exchange volume on
    any graph with self-loops (where the uniform layout saturates
    Mb == vp). Autodiffs natively, like the uniform fused op. ``tables``
    is ``sm.shard(mesh)``; ``sm`` gives the sizes (the SplitMirror, or the
    tables themselves, which carry them)."""
    t = tables
    P, mb, vp = sm.partitions, sm.mb, sm.vp

    def body(need, r_slot, r_dst, r_w, r_mask, l_src, l_dst, l_w, l_mask, xs):
        rows = _gather_rows(need[0], xs)  # [P, mb, f]
        got = lax.all_to_all(rows, PARTITION_AXIS, 0, 0, tiled=True)
        mirrors = got.reshape(P * mb, xs.shape[1])
        return _split_aggregate_body(
            vp, r_slot[0], r_dst[0], r_mask[0], r_w[0],
            l_src[0], l_dst[0], l_mask[0], l_w[0], mirrors, xs,
        )

    fn = _shmap(
        body,
        mesh,
        in_specs=(
            PS(PARTITION_AXIS, None, None),
            _E2, _E2, _E2, _E2, _E2, _E2, _E2, _E2,
            PS(PARTITION_AXIS, None),
        ),
        out_specs=PS(PARTITION_AXIS, None),
    )
    return fn(
        t.need_ids, t.r_src_slot, t.r_dst, t.r_weight, t.r_mask, t.l_src,
        t.l_dst, t.l_weight, t.l_mask, x,
    )


def dist_gather_dst_from_src_mirror_split_sim(sm, x: jax.Array) -> jax.Array:
    """Collective-free twin of the split fused op (single-core rig)."""
    P, mb, vp = sm.partitions, sm.mb, sm.vp
    xs = x.reshape(P, vp, -1)
    rows = jax.vmap(_gather_rows)(jnp.asarray(sm.need_ids), xs)  # [P,P,mb,f]
    mirrors = jnp.swapaxes(rows, 0, 1).reshape(P, P * mb, -1)

    def per_dev(p):
        return _split_aggregate_body(
            vp,
            jnp.asarray(sm.r_src_slot[p]), jnp.asarray(sm.r_dst[p]),
            jnp.asarray(sm.r_mask[p]), jnp.asarray(sm.r_weight[p]),
            jnp.asarray(sm.l_src[p]), jnp.asarray(sm.l_dst[p]),
            jnp.asarray(sm.l_mask[p]), jnp.asarray(sm.l_weight[p]),
            mirrors[p], xs[p],
        )

    return jnp.concatenate([per_dev(p) for p in range(P)], axis=0)


# ---------------------------------------------------------------------------
# simulated (single-core) whole-chain variants
# ---------------------------------------------------------------------------

def _vmap_tables(mg: MirrorGraph):
    return (
        jnp.asarray(mg.edge_src_slot),
        jnp.asarray(mg.edge_dst),
        jnp.asarray(mg.edge_weight),
        jnp.asarray(mg.edge_mask),
    )


def dist_scatter_src_sim(mg, mirrors):
    slot, _, _, mask = _vmap_tables(mg)
    return jax.vmap(_scatter_src_body)(slot, mask, mirrors)


def dist_scatter_dst_sim(mg, x):
    _, dst, _, mask = _vmap_tables(mg)
    xs = x.reshape(mg.partitions, mg.vp, -1)
    return jax.vmap(_scatter_dst_body)(dst, mask, xs)


def dist_edge_softmax_sim(mg, score):
    _, dst, _, mask = _vmap_tables(mg)
    return jax.vmap(partial(_edge_softmax_body, mg.vp))(dst, mask, score)


def dist_aggregate_dst_sim(mg, edge_vals):
    _, dst, _, mask = _vmap_tables(mg)
    out = jax.vmap(partial(_aggregate_dst_body, mg.vp))(dst, mask, edge_vals)
    return out.reshape(mg.padded_v, -1)


def dist_aggregate_dst_max_sim(mg, edge_vals):
    _, dst, _, mask = _vmap_tables(mg)
    out = jax.vmap(partial(_extreme_body, mg.vp, False))(dst, mask, edge_vals)
    return out.reshape(mg.padded_v, -1)


def dist_aggregate_dst_min_sim(mg, edge_vals):
    _, dst, _, mask = _vmap_tables(mg)
    out = jax.vmap(partial(_extreme_body, mg.vp, True))(dst, mask, edge_vals)
    return out.reshape(mg.padded_v, -1)


def dist_aggregate_dst_fuse_weight_sim(mg, edge_weight, mirrors):
    slot, dst, _, mask = _vmap_tables(mg)
    out = jax.vmap(partial(_fuse_weight_body, mg.vp))(
        slot, dst, mask, edge_weight, mirrors
    )
    return out.reshape(mg.padded_v, -1)


def dist_gather_dst_from_src_mirror_sim(mg, x):
    mirrors = dist_get_dep_nbr_sim(mg, x)
    return dist_aggregate_dst_fuse_weight_sim(mg, jnp.asarray(mg.edge_weight), mirrors)
