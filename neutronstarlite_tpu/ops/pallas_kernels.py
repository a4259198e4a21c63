"""Pallas TPU kernels for the fused neighbor aggregation.

The reference's CUDA analog is aggregate_kernel_from_src_with_weight[_optim]
(cuda/ntsCUDAFuseKernel.cuh:147-293): one fused kernel doing gather ->
scale-by-edge-weight -> per-dst accumulate, shared-memory tiled. The TPU
counterpart here operates on the ELL layout (ops/ell.py) — the gather-only
formulation measured 2.5x faster than scatter on real v5e (docs/PERF.md
section 2) — and fuses gather + scale + K-reduction in VMEM:

- ``ell_aggregate_pallas``: grid over row tiles of one [Nk, K] bucket
  level; each step holds an [R, K] neighbor/weight tile and the full
  [V, f] feature table in VMEM, gathers rows with a vectorized VMEM
  gather (one ``x[idx]`` per K column — K is static per level), and
  writes the f32-accumulated row sums. No HBM round-trips for
  intermediates; no serial per-edge loop (the round-1 prototype's flaw).
- ``gather_dst_from_src_pallas``: applies the kernel per bucket level and
  assembles with the inverse permutation — a drop-in twin of
  ``ops.ell.ell_gather_dst_from_src``'s forward.

**STATUS (round 3, discovered via topology AOT compiles 2026-07-31):
interpret-mode / design-study only — this kernel cannot lower to Mosaic.**
The TPU's only vectorized gather (``tpu.dynamic_gather``, exposed through
``jnp.take_along_axis``) is an ELEMENTWISE shuffle whose input, index and
output shapes must all match (jax/_src/pallas/mosaic/lowering.py's
lax.gather rule); a row gather ``x[idx]`` from a resident [V, f] table —
the core of this kernel — has out rows != V and is rejected for every
(rows, K, V, f) shape tested. There is no VMEM-resident random-row-gather
primitive to build on, so the whole "table resident, gather on-chip"
regime is unimplementable in compiled Pallas on this stack; the full-scale
bench legs that tried compiled ~50-kernel epochs of this design never
returned (the remote compile service hangs rather than surfacing the
ValueError). The PRODUCTION fused aggregation is ops/bsp_ell.py — the
(dst-tile, src-tile) streamed block-sparse kernel whose per-block combine
is a one-hot MXU matmul, i.e. the one fused design that needs NO gather
at all; ``PALLAS:1`` routes there (models/fullbatch.py). This module
remains as the interpret-mode twin (semantics tests, CPU CI) and the
written record of the regime analysis: feature-column chunking, level
merging and the VMEM budget math below are correct FOR THE DESIGN and
would apply directly should Mosaic grow a row-gather primitive.
"""

from __future__ import annotations

import dataclasses
import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from neutronstarlite_tpu.ops.ell import (
    EllBuckets,
    EllPair,
    ell_tables_aggregate,
)

DEFAULT_ROW_TILE = 512
_K_CHUNK = 8  # static inner unroll; K beyond this iterates a fori_loop
# bucket levels wider than this stay on the XLA path (row-vectorized kernel
# degrades to a serial K loop on few-row hub levels; Reddit-scale power-law
# graphs carry a K ~ 2^21 supernode bucket)
MAX_PALLAS_K = 1024
# the kernel holds the gathered [V, fc] table in VMEM; wider inputs are
# feature-column-chunked to fit (v5e VMEM = 128 MB, minus tile double
# buffers); only when the ROW count alone exceeds the budget does the call
# degrade to the XLA ELL path instead of failing Mosaic's VMEM allocation
MAX_TABLE_BYTES = 96 << 20
# levels with K below this are merged into one K=PALLAS_MIN_K level at
# PallasEllPair build time (round-3 hang postmortem): every (rows, K, f)
# triple is a distinct Mosaic compile, and at full Reddit scale ~22 bucket
# levels x f-chunks x fwd/bwd directions stacked ~50 kernel compiles into
# ONE jitted epoch program — aggregate compile time through the remote
# compile service blew the 1200 s measurement window with nothing
# persisted (the executable cache is whole-program). Low-K levels hold few
# SLOTS on power-law graphs (rows with deg <= 64 contribute << E slots),
# so padding them up is a few percent of slot traffic in exchange for
# ~halving the distinct-kernel count. 0 disables. Numerically exact:
# padding slots carry weight 0 into an f32 accumulation.
PALLAS_MIN_K = int(os.environ.get("NTS_PALLAS_MIN_K", "64"))


def _ell_level_kernel(nbr_ref, wgt_ref, x_ref, o_ref, *, k_cols: int):
    """One row tile of one bucket level: o[r] = sum_k w[r,k] * x[nbr[r,k]].

    nbr/wgt [R, K] and x [V, f] live in VMEM; the gather is a vectorized
    VMEM row gather per K column. K columns are walked by a fori_loop over
    _K_CHUNK-wide slices (static inner unroll) so high-degree bucket levels
    (K = next_pow2(max_degree), tens of thousands on power-law graphs) do
    not unroll into K separate ops. Products and accumulation are f32 in
    registers — the identical numeric policy to
    ops.ell.ell_tables_aggregate's row_sum."""
    x = x_ref[:]
    rows = nbr_ref.shape[0]
    f = x.shape[1]
    kc = min(_K_CHUNK, k_cols)
    n_blocks = k_cols // kc  # call site pads K to a _K_CHUNK multiple

    def block(b, acc):
        nb = nbr_ref[:, pl.ds(b * kc, kc)]
        wb = wgt_ref[:, pl.ds(b * kc, kc)]
        for j in range(kc):
            acc = acc + x[nb[:, j]].astype(jnp.float32) * wb[:, j][:, None]
        return acc

    acc = jax.lax.fori_loop(
        0, n_blocks, block, jnp.zeros((rows, f), jnp.float32)
    )
    o_ref[:] = acc.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("row_tile", "interpret"))
def ell_aggregate_pallas(
    nbr: jax.Array,  # [Nk, K] int32 neighbor ids (0 + weight 0 on padding)
    wgt: jax.Array,  # [Nk, K] f32 weights
    x: jax.Array,  # [V, f]
    row_tile: int = DEFAULT_ROW_TILE,
    interpret: bool = False,
) -> jax.Array:
    """[Nk, K] ELL tables + [V, f] features -> [Nk, f] row sums."""
    n_rows, k_cols = nbr.shape
    v_num, f = x.shape
    rt = min(row_tile, n_rows)
    pad = (-n_rows) % rt
    kpad = (-k_cols) % min(_K_CHUNK, k_cols) if k_cols else 0
    if pad or kpad:
        # padding slots carry weight 0 and index row 0: contribute nothing
        nbr = jnp.pad(nbr, ((0, pad), (0, kpad)))
        wgt = jnp.pad(wgt, ((0, pad), (0, kpad)))
    k_cols += kpad
    grid = ((n_rows + pad) // rt,)

    out = pl.pallas_call(
        functools.partial(_ell_level_kernel, k_cols=k_cols),
        out_shape=jax.ShapeDtypeStruct((n_rows + pad, f), x.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((rt, k_cols), lambda i: (i, 0)),
            pl.BlockSpec((rt, k_cols), lambda i: (i, 0)),
            pl.BlockSpec((v_num, f), lambda i: (0, 0)),  # x resident
        ],
        out_specs=pl.BlockSpec((rt, f), lambda i: (i, 0)),
        interpret=interpret,
    )(nbr, wgt, x)
    return out[:n_rows]


def merge_level_tables(nbrs, wgts, min_k: int, row_axis: int = 0):
    """Merge every level with 0 < K <= min_k into ONE level padded to
    K=min_k: pad the (last) K axis, concatenate rows along ``row_axis``.
    Consecutive levels concatenate in their original order, so the
    concatenated output rows — and therefore any inv row map over them —
    are untouched; padding slots carry neighbor 0 with weight 0 and
    contribute nothing (the module-constant rationale explains why fewer
    levels matter: one Mosaic compile per (rows, K, f) triple). The K=0
    zero-degree level stays separate: merging it would buy slots for rows
    with no edges at all. Serves both the 2D EllBuckets tables
    (row_axis=0) and the stacked [P, Nk, K] dist tables (row_axis=1)."""
    if min_k <= 0:
        return list(nbrs), list(wgts)
    merged_nbr, merged_wgt = [], []
    group_n, group_w = [], []
    for nbr, wgt in zip(nbrs, wgts):
        k = nbr.shape[-1]
        if 0 < k <= min_k:
            pad = [(0, 0)] * nbr.ndim
            pad[-1] = (0, min_k - k)
            group_n.append(jnp.pad(nbr, pad))
            group_w.append(jnp.pad(wgt, pad))
            continue
        # levels arrive in increasing K, so the low-K group is a prefix
        # (after the optional K=0 level) — flush before any wider level
        if group_n:
            merged_nbr.append(jnp.concatenate(group_n, axis=row_axis))
            merged_wgt.append(jnp.concatenate(group_w, axis=row_axis))
            group_n, group_w = [], []
        merged_nbr.append(nbr)
        merged_wgt.append(wgt)
    if group_n:
        merged_nbr.append(jnp.concatenate(group_n, axis=row_axis))
        merged_wgt.append(jnp.concatenate(group_w, axis=row_axis))
    return merged_nbr, merged_wgt


def _next_pow2(x: int) -> int:
    return 1 << max(int(x) - 1, 0).bit_length()


def effective_min_k(total_slots: int, n_rows: int, min_k: int) -> int:
    """Cap the merge threshold at the graph's own degree scale: merging to
    K=64 on a mean-degree-5 graph (Cora) pads ~15x, while on mean-degree-
    492 Reddit the same merge costs a few percent. next-pow2 of the mean
    slot count per row keeps the compile-count win where slots are dense
    and bounds the padding where they are not (mean is computed over the
    already-padded tables, so it upper-bounds the real mean degree)."""
    if min_k <= 0 or n_rows <= 0:
        return min_k
    return min(min_k, _next_pow2(max(total_slots // n_rows, 1)))


def merge_low_k_levels(buckets: EllBuckets, min_k: int) -> EllBuckets:
    """EllBuckets wrapper of ``merge_level_tables`` (row_axis=0). ``min_k``
    is applied literally — degree-adaptive capping is the POLICY sites'
    job (PallasEllPair.from_pair, parallel/dist_ell.DistEllPair.build via
    ``effective_min_k``), not this mechanism's."""
    if min_k <= 0:
        return buckets
    merged_nbr, merged_wgt = merge_level_tables(
        buckets.nbr, buckets.wgt, min_k, row_axis=0
    )
    return EllBuckets(
        nbr=merged_nbr, wgt=merged_wgt, inv_perm=buckets.inv_perm,
        v_num=buckets.v_num, slot_chunk=buckets.slot_chunk,
    )


def gather_dst_from_src_pallas(
    ell_pair_or_buckets,
    x: jax.Array,
    row_tile: int = DEFAULT_ROW_TILE,
    interpret: bool = False,
) -> jax.Array:
    """Fused CSC aggregation out[v] = sum_{(u->v)} w_uv * x[u] over the ELL
    bucket layout (ops.ell.EllPair or EllBuckets). Forward only — for
    training use ``pallas_gather_dst_from_src`` (PallasEllPair), whose
    custom_vjp pairs this kernel with its transpose tables."""
    buckets: EllBuckets = (
        ell_pair_or_buckets.fwd
        if isinstance(ell_pair_or_buckets, EllPair)
        else ell_pair_or_buckets
    )
    return pallas_tables_aggregate(
        x, buckets.nbr, buckets.wgt, buckets.slot_chunk,
        row_tile=row_tile, interpret=interpret,
    )[buckets.inv_perm]


def pallas_tables_aggregate(
    x: jax.Array,
    nbrs,
    wgts,
    slot_chunk: int,
    row_tile: int = DEFAULT_ROW_TILE,
    interpret: bool = False,
) -> jax.Array:
    """Level-table twin of ``ops.ell.ell_tables_aggregate`` running the
    fused kernel per level (callers apply their own inv_perm) — the shared
    executor for the single-chip path above and the distributed per-shard
    path (parallel/dist_ell.py with kernel='pallas')."""
    v_num, f = x.shape
    if v_num * f * x.dtype.itemsize > MAX_TABLE_BYTES:
        # wider than the VMEM budget: chunk the FEATURE dim so each chunk's
        # [V, fc] table is resident — the tables are re-read per chunk but
        # every gather stays on-chip (module docstring, round-3 change)
        fc = (MAX_TABLE_BYTES // (v_num * x.dtype.itemsize)) // 128 * 128
        if fc == 0:
            # the ROW count alone exceeds the budget (V > ~375k rows in
            # bf16): single-chip beyond-VMEM graphs route to the XLA path
            # here; ops/bsp_ell.py is the Pallas kernel for that regime
            return ell_tables_aggregate(x, nbrs, wgts, slot_chunk)
        # pad f up to a chunk multiple first so EVERY chunk call shares one
        # [V, fc] shape — a ragged tail chunk (602 = 4*128 + 90) would be
        # its own Mosaic compile for every level (round-3 hang postmortem)
        fpad = (-f) % fc
        if fpad:
            x = jnp.pad(x, ((0, 0), (0, fpad)))
        return jnp.concatenate(
            [
                pallas_tables_aggregate(
                    x[:, lo: lo + fc], nbrs, wgts, slot_chunk,
                    row_tile=row_tile, interpret=interpret,
                )
                for lo in range(0, f + fpad, fc)
            ],
            axis=1,
        )[:, :f]
    outs = []
    for nbr, wgt in zip(nbrs, wgts):
        if nbr.shape[1] == 0:
            # zero-degree bucket: zero rows, no kernel launch
            outs.append(jnp.zeros((nbr.shape[0], x.shape[1]), x.dtype))
        elif nbr.shape[1] > MAX_PALLAS_K:
            # hub tail: the kernel vectorizes over rows and loops K, so a
            # [few rows, K ~ 2^21] level (a power-law supernode bucket)
            # would serialize; its XLA gather+reduce vectorizes over K
            outs.append(ell_tables_aggregate(x, [nbr], [wgt], slot_chunk))
        else:
            outs.append(
                ell_aggregate_pallas(
                    nbr, wgt, x, row_tile=row_tile, interpret=interpret
                )
            )
    return jnp.concatenate(outs, axis=0)


# ---- trainable Pallas backend (KERNEL selection: PALLAS:1) -----------------


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class PallasEllPair:
    """EllPair twin whose aggregation runs the fused Pallas kernel.

    Same numeric policy and custom_vjp transpose pairing as ops.ell.EllPair;
    the tables differ in one build-time transform: levels with K <=
    PALLAS_MIN_K are merged into a single K=PALLAS_MIN_K level (fewer
    Mosaic compiles — see merge_low_k_levels; numerically exact). The
    per-level executor is the VMEM-resident vectorized gather kernel
    instead of XLA gather+reduce; hub levels wider than MAX_PALLAS_K still
    route to XLA, see gather_dst_from_src_pallas.
    Regime: the gathered [V, fc] table must fit the VMEM budget per
    feature-column chunk — any width works (wide layers are column-chunked,
    re-reading the tables per chunk), so both the EAGER order
    (GCN_CPU_EAGER.hpp:200-206 analog) and the full-scale STANDARD order
    (602-wide layer 1) run fused. The row count is the remaining bound
    (V <= ~375k rows bf16); past it the XLA path serves, or ops/bsp_ell.py.
    Off-TPU (tests, CPU CI) the kernel runs in interpret mode.
    """

    fwd: EllBuckets
    bwd: EllBuckets
    row_tile: int = dataclasses.field(
        default=DEFAULT_ROW_TILE, metadata=dict(static=True)
    )

    @staticmethod
    def from_host(g, row_tile: int = DEFAULT_ROW_TILE) -> "PallasEllPair":
        return PallasEllPair.from_pair(EllPair.from_host(g), row_tile)

    @staticmethod
    def from_pair(pair: EllPair, row_tile: int = DEFAULT_ROW_TILE) -> "PallasEllPair":
        def adaptive(buckets: EllBuckets) -> EllBuckets:
            slots = sum(int(n.shape[0] * n.shape[1]) for n in buckets.nbr)
            rows = sum(int(n.shape[0]) for n in buckets.nbr)
            return merge_low_k_levels(
                buckets, effective_min_k(slots, rows, PALLAS_MIN_K)
            )

        return PallasEllPair(
            fwd=adaptive(pair.fwd),
            bwd=adaptive(pair.bwd),
            row_tile=int(row_tile),
        )


def pallas_interpret_default() -> bool:
    """interpret everywhere the default backend can't lower Mosaic — keeps
    the CPU suite exercising the same code path the chip runs.
    NTS_PALLAS_FORCE_COMPILED=1 overrides for AOT lowering against a TPU
    TOPOLOGY from a CPU host (tools/aot_bench_path): tracing never executes
    the kernel, and the topology compiler consumes the Mosaic call."""
    if os.environ.get("NTS_PALLAS_FORCE_COMPILED", "0") == "1":
        return False
    return jax.default_backend() not in ("tpu",)


def _apply_buckets(buckets: EllBuckets, x: jax.Array, row_tile: int) -> jax.Array:
    return gather_dst_from_src_pallas(
        buckets, x, row_tile=row_tile, interpret=pallas_interpret_default()
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _pallas_pair_aggregate(row_tile, fwd, bwd, x):
    return _apply_buckets(fwd, x, row_tile)


def _pallas_pair_aggregate_fwd(row_tile, fwd, bwd, x):
    return _apply_buckets(fwd, x, row_tile), (fwd, bwd)


def _pallas_pair_aggregate_bwd(row_tile, res, g):
    from neutronstarlite_tpu.ops.segment import zero_cotangent

    fwd, bwd = res
    zero = jax.tree.map(zero_cotangent, (fwd, bwd))
    return (*zero, _apply_buckets(bwd, g, row_tile))


_pallas_pair_aggregate.defvjp(_pallas_pair_aggregate_fwd, _pallas_pair_aggregate_bwd)


def pallas_gather_dst_from_src(pair: PallasEllPair, x: jax.Array) -> jax.Array:
    """Fused-kernel weighted aggregation (custom_vjp pairs the transpose)."""
    return _pallas_pair_aggregate(pair.row_tile, pair.fwd, pair.bwd, x)


def pallas_gather_src_from_dst(pair: PallasEllPair, y: jax.Array) -> jax.Array:
    """The CSR direction as a forward op."""
    return _pallas_pair_aggregate(pair.row_tile, pair.bwd, pair.fwd, y)
