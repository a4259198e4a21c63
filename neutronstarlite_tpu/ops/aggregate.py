"""Neighbor aggregation operators with hand-paired backward passes.

These are the TPU counterparts of the reference's fused aggregation kernels:

- ``gather_dst_from_src``: forward CSC aggregation out[dst] += w * x[src]
  (reference: GatherByDstFromSrc -> aggregate_kernel_from_src_with_weight,
  cuda/ntsCUDAFuseKernel.cuh:147; CPU nts_comp loop,
  core/ntsCPUFusedGraphOp.hpp:88-105). Its custom_vjp backward runs the CSR
  (src-sorted) aggregation of the output gradient — exactly the pairing the
  reference hand-writes (GatherBySrcFromDst, ntsCUDAFuseKernel.cuh:327;
  process_edges_backward engines).
- ``gather_src_from_dst``: the CSR direction exposed as a forward op.
- ``aggregate_dst_min`` / ``aggregate_dst_max``: elementwise min/max with
  arg-extreme routing in the backward, mirroring SingleCPUDstAggregateOpMin/Max
  (core/ntsSingleCPUGraphOp.hpp:206/:274) whose ``record`` array routes the
  gradient to the winning edge.

Implementation notes (TPU-first): the hot op never materializes the [E, f]
gathered-feature intermediate for large graphs — it scans fixed-size edge
chunks, each chunk doing gather -> scale -> scatter-add into the [V, f]
accumulator. Edge arrays are pre-sorted (CSC by dst, CSR by src) so the
scatter-add carries ``indices_are_sorted``; padding edges have weight 0 and
point at vertex 0, contributing nothing. This replaces the reference's
work-stealing/omp-chunk machinery (graph.hpp:2005-2041) with static chunking
decided at preprocessing time.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from neutronstarlite_tpu.ops.device_graph import DeviceGraph
from neutronstarlite_tpu.ops.segment import zero_cotangent


def _scatter_accumulate(
    src, dst, weight, x, v_num: int, edge_chunk: int, acc_dtype, acc=None
):
    """sum over edges of weight_e * x[src_e] into [v_num, f], chunked.

    ``src``/``dst``/``weight`` are [Ep] with Ep a multiple of edge_chunk and
    indices sorted by ``dst``. An existing accumulator may be passed (the
    distributed ring adds one partial per ring step into the same output).
    """
    e_pad = src.shape[0]
    f = x.shape[1]
    n_chunks = e_pad // edge_chunk
    if acc is None:
        acc = jnp.zeros((v_num, f), dtype=acc_dtype)

    def chunk_add(carry, s, d, w):
        vals = x[s] * w[:, None].astype(x.dtype)
        return carry.at[d].add(
            vals.astype(acc_dtype), indices_are_sorted=True, unique_indices=False
        )

    if n_chunks <= 1:
        return chunk_add(acc, src, dst, weight)

    # The first chunk is applied outside the scan: under shard_map the
    # zeros-initialized accumulator is unvarying over the mesh axis while the
    # scan body's output (which mixes in the sharded edge data) is varying,
    # and lax.scan requires carry-in == carry-out varying types. One
    # data-dependent update makes the carry varying without naming the mesh
    # axis here (this op runs both inside and outside shard_map).
    acc = chunk_add(acc, src[:edge_chunk], dst[:edge_chunk], weight[:edge_chunk])

    def body(carry, chunk):
        s, d, w = chunk
        return chunk_add(carry, s, d, w), None

    chunks = (
        src[edge_chunk:].reshape(n_chunks - 1, edge_chunk),
        dst[edge_chunk:].reshape(n_chunks - 1, edge_chunk),
        weight[edge_chunk:].reshape(n_chunks - 1, edge_chunk),
    )
    acc, _ = lax.scan(body, acc, chunks)
    return acc


@partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _aggregate(v_num, edge_chunk, fwd_src, fwd_dst, fwd_w, bwd_src, bwd_dst, bwd_w, x):
    return _scatter_accumulate(fwd_src, fwd_dst, fwd_w, x, v_num, edge_chunk, x.dtype)


def _aggregate_fwd(v_num, edge_chunk, fwd_src, fwd_dst, fwd_w, bwd_src, bwd_dst, bwd_w, x):
    out = _scatter_accumulate(fwd_src, fwd_dst, fwd_w, x, v_num, edge_chunk, x.dtype)
    return out, (fwd_src, fwd_dst, fwd_w, bwd_src, bwd_dst, bwd_w)


def _aggregate_bwd(v_num, edge_chunk, res, g):
    fwd_src, fwd_dst, fwd_w, bwd_src, bwd_dst, bwd_w = res
    # The paired backward: aggregate the output gradient along the reverse
    # (src-sorted) adjacency — grad_x[src] += w * g[dst].
    grad_x = _scatter_accumulate(bwd_dst, bwd_src, bwd_w, g, v_num, edge_chunk, g.dtype)
    return (
        zero_cotangent(fwd_src),
        zero_cotangent(fwd_dst),
        zero_cotangent(fwd_w),
        zero_cotangent(bwd_src),
        zero_cotangent(bwd_dst),
        zero_cotangent(bwd_w),
        grad_x,
    )


_aggregate.defvjp(_aggregate_fwd, _aggregate_bwd)


_LANE_WIDTH = 128
# the cliff only manifests at full scale (docs/PERF.md section 2a: 5%
# scale shows eager within 2% of standard; full scale shows 15x) — below
# this edge count the fence would only tax small runs with pad traffic
_LANE_PAD_MIN_EDGES = 1 << 20
_lane_pad_logged: set = set()


def _lane_pad_width(f: int, e_pad: int) -> int:
    """The eager/scatter full-scale cliff fence (docs/PERF.md section 2a:
    eager/scatter measured 15x slower than standard/scatter at full Reddit
    scale ONLY — the 41-wide scatter-add over 114.6M updates falls out of
    XLA's vectorized sorted-update regime below the 128-lane width).
    Fix: pad narrow features to the lane width before the scatter and
    slice after — 3x slot traffic at f=41 in exchange for the vectorized
    regime. ON by default for full-scale scatters (>= _LANE_PAD_MIN_EDGES
    padded edges); NTS_SCATTER_LANE_PAD=1 forces it at any size,
    NTS_SCATTER_LANE_PAD=0 disables it — with a one-line warning either
    way, so the 110-vs-7-second regression can't silently return."""
    import os

    from neutronstarlite_tpu.utils.logging import get_logger

    if f >= _LANE_WIDTH:
        return f
    mode = os.environ.get("NTS_SCATTER_LANE_PAD", "auto")
    log = get_logger("aggregate")
    if mode not in ("", "auto", "0", "1"):
        # historical semantics: any non-"1" value disabled the fence, so
        # an existing opt-out spelling (false/off/no) must keep opting
        # out when the default flips to auto — but say so, loudly
        if ("spelling", mode) not in _lane_pad_logged:
            _lane_pad_logged.add(("spelling", mode))
            log.warning(
                "NTS_SCATTER_LANE_PAD=%r is not a recognized value "
                "(use 0/1/auto); treating it as 0 (fence off) for "
                "backward compatibility", mode,
            )
        mode = "0"
    if mode == "0":
        if e_pad >= _LANE_PAD_MIN_EDGES and ("off", f) not in _lane_pad_logged:
            _lane_pad_logged.add(("off", f))
            log.warning(
                "narrow scatter width %d < lane width %d over %d edges "
                "with NTS_SCATTER_LANE_PAD=0 — this is the PERF.md "
                "section-2a 15x regime; expect a serialized scatter", f,
                _LANE_WIDTH, e_pad,
            )
        return f
    if mode != "1" and e_pad < _LANE_PAD_MIN_EDGES:
        return f
    if ("pad", f) not in _lane_pad_logged:
        _lane_pad_logged.add(("pad", f))
        log.warning(
            "scatter width %d below the %d-lane width over %d edges: "
            "routing through lane padding (%.1fx slot traffic; "
            "NTS_SCATTER_LANE_PAD=0 opts out)", f, _LANE_WIDTH, e_pad,
            _LANE_WIDTH / max(f, 1),
        )
    return _LANE_WIDTH


def build_tables(cfg, host_graph):
    """The single-chip ``OPTIM_KERNEL:1`` table pair for ``cfg``, and its
    padding stats where the layout has levels to report (the ELL's; else
    None). The ONE place a cfg becomes a single-chip table type: every
    full-batch trainer reaches it through ``FullBatchTrainer.build_model``,
    bench.py and the AOT tools through the trainer.

    - ``PALLAS:1``: the streamed block-sparse Mosaic kernel (ops/bsp_ell),
      ``KERNEL_TILE`` its src-tile height;
    - ``KERNEL_TILE:vt`` alone: source-tiled blocked ELL (ops/blocked_ell);
    - else the gather-only ELL levels (ops/ell), what the benchmark runs.

    Without ``OPTIM_KERNEL:1`` a trainer aggregates over the DeviceGraph
    scatter path and never calls this (``ToolkitBase._check_kernel``
    refuses ``PALLAS:1`` there)."""
    # each branch imports its own layout: the caller times this call as its
    # tables_build phase, and ops/bsp_ell brings in Pallas (over a second)
    if cfg.pallas_kernel:
        from neutronstarlite_tpu.ops.bsp_ell import BspEllPair

        tile = {"vt": cfg.kernel_tile} if cfg.kernel_tile > 0 else {}
        return BspEllPair.from_host(host_graph, **tile), None
    if cfg.kernel_tile > 0:
        from neutronstarlite_tpu.ops.blocked_ell import BlockedEllPair

        return BlockedEllPair.from_host(host_graph, vt=cfg.kernel_tile), None
    from neutronstarlite_tpu.ops.ell import EllPair

    pair = EllPair.from_host(host_graph)
    return pair, pair.padding_stats(host_graph.e_num)


def gather_dst_from_src(graph, x: jax.Array) -> jax.Array:
    """out[v] = sum over in-edges (u -> v) of w_uv * x[u].  [V, f] -> [V, f].

    A table pair (``build_tables``) aggregates itself; a DeviceGraph runs
    the chunked sorted-scatter path below, the reference every layout is
    tested against."""
    if not isinstance(graph, DeviceGraph):
        return graph.gather_dst_from_src(x)
    f = x.shape[1]
    fp = _lane_pad_width(f, int(graph.csc_src.shape[0]))
    if fp != f:
        x = jnp.pad(x, ((0, 0), (0, fp - f)))
    out = _aggregate(
        graph.v_num,
        graph.edge_chunk,
        graph.csc_src,
        graph.csc_dst,
        graph.csc_weight,
        graph.csr_src,
        graph.csr_dst,
        graph.csr_weight,
        x,
    )
    return out[:, :f] if fp != f else out


def gather_src_from_dst(graph, y: jax.Array) -> jax.Array:
    """out[u] = sum over out-edges (u -> v) of w_uv * y[v] — the CSR direction
    (the reference's backward engine, exposed as a forward op)."""
    if not isinstance(graph, DeviceGraph):
        return graph.gather_src_from_dst(y)
    # same narrow-width fence as the CSC direction (the scatter regime is
    # direction-agnostic)
    f = y.shape[1]
    fp = _lane_pad_width(f, int(graph.csr_dst.shape[0]))
    if fp != f:
        y = jnp.pad(y, ((0, 0), (0, fp - f)))
    out = _aggregate(
        graph.v_num,
        graph.edge_chunk,
        graph.csr_dst,
        graph.csr_src,
        graph.csr_weight,
        graph.csc_dst,
        graph.csc_src,
        graph.csc_weight,
        y,
    )
    return out[:, :f] if fp != f else out


def aggregate_dst_max(graph: DeviceGraph, x: jax.Array) -> jax.Array:
    """Elementwise max over in-neighbors; gradient routed to the winning
    edge's source (SingleCPUDstAggregateOpMax + its ``record`` routing,
    core/ntsSingleCPUGraphOp.hpp:274). Composition of the V->E gather with
    the shared masked-extreme core (ops/edge.py); the gather's autodiff
    transpose is the edge->source scatter-add. Not chunked: materializes
    [Ep, f] edge values — the edge-op model family path, not the
    Reddit-scale hot path."""
    from neutronstarlite_tpu.ops.edge import _edge_extreme

    return _edge_extreme(
        graph.v_num, False, graph.csc_dst, graph.edge_mask, x[graph.csc_src]
    )


def aggregate_dst_min(graph: DeviceGraph, x: jax.Array) -> jax.Array:
    """Elementwise min over in-neighbors (SingleCPUDstAggregateOpMin,
    core/ntsSingleCPUGraphOp.hpp:206)."""
    from neutronstarlite_tpu.ops.edge import _edge_extreme

    return _edge_extreme(
        graph.v_num, True, graph.csc_dst, graph.edge_mask, x[graph.csc_src]
    )
