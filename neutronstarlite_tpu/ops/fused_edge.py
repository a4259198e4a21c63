"""Fused SDDMM + online softmax + SpMM: the blocked edge kernel for the
attention/edge-op families (GAT / GGCN).

The eager edge-op chain (models/gat.py, models/ggcn.py over ops/edge.py)
runs the paper's decoupled operator sequence literally: ``scatter_src_to_
edge`` materializes a padded [Ep, f] edge tensor in HBM, ``edge_softmax``
makes two more [Ep]-shaped passes (segment max + segment sum), and
``aggregate_edge_to_dst_weighted`` reads the edge space back — three HBM
round-trips of edge-width tensors per layer, the traffic class the GCN
family already avoids via the blocked kernels. FusedMM (PAPERS.md) shows
the SDDMM (edge-score) and SpMM (aggregate) phases fuse into one kernel
with no edge-tensor round-trip; this module is that fusion re-derived for
the streamed-block regime of ops/blocked_ell.py / ops/bsp_ell.py:

- the source space is cut into tiles of ``vt`` rows; per (src-tile,
  dst-run) block the tables hold tile-LOCAL source ids, so every gather
  indexes a [vt, .] resident slab (the ops/ell.py on-chip-gather premise;
  a Mosaic/Pallas lowering of the same schedule would build the scores as
  one-hot MXU matmuls against these tables — the bsp_ell one-hot regime —
  because Mosaic has no row gather, docs/PERF.md section 5. The XLA
  blocked form ships first: it compiles everywhere, pays no dt*f FLOPs
  per row for the scatter matmul, and fixes the same HBM envelope);
- the per-destination softmax is ONLINE (flash-attention style): a
  running (max m, normalizer l, weighted accumulator acc) per destination
  is carried across source tiles; each block rescales the carried state
  by exp(m_old - m_new) and folds its exp-scores in, so no [Ep]-shaped
  score/alpha tensor ever exists — the jaxpr of the fused forward has no
  Ep x f aval (pinned by tests/test_fused_edge.py);
- the backward is hand-paired (custom_vjp): the softmax Jacobian
  ``s * (g - sum_dst(s * g))`` is recomputed BLOCKWISE from the saved
  (m, l) statistics — three streamed passes (per-dst Jacobian sum T1 over
  the forward tables; dst-half score gradient over the forward tables;
  feature + src-half gradients over the TRANSPOSED tables, the CSR
  direction tiled by destination) — never an [Ep, f] intermediate.

Two score layouts serve both model families through ONE code path,
selected by the channel width C of the score halves:

- GAT  (C = 1): score(e) = leaky_relu(asrc[src] + adst[dst]), a scalar
  per edge; softmax per destination; out[d] = sum_e s_e * h[src].
- GGCN (C = f): per-CHANNEL scores/softmax (the gated-GCN chain), same
  expressions with elementwise [.., C] broadcasting.

``asrc``/``adst`` are the decomposed per-vertex score halves the models
already compute as MXU matmuls (a . [h_src||h_dst] = a_src.h_src +
a_dst.h_dst — the reference's own GAT_CPU_DIST_OPTM trick), so gradients
to the attention parameters flow through those matmuls from the
``grad_asrc``/``grad_adst`` this op returns.

Numeric policy matches ops/blocked_ell.py: f32 state (m, l, acc) and f32
products regardless of input dtype, one cast at the end. Empty
destinations (no real in-edges, incl. all-padding rows) produce EXACT
zeros — the ops/edge.edge_softmax convention, pinned by regression test.

Tables are BlockedEll pairs built with unit weights (the attention family
is weight_mode "ones"; the table weights serve as the validity mask) and
degree-binned levels by default (blocked_ell.resolve_levels). The
distributed ring form (parallel/dist_fused_edge.py) carries the SAME
(m, l, acc) state across ring hops — the aggregate_into-style f32 carry —
so the online softmax extends across partitions with no extra exchange.

Enable per-trainer with ``KERNEL:fused_edge`` (cfg); the eager edge chain
stays the parity oracle (tests/test_fused_edge.py sweeps forward and
backward, f32/bf16, GAT/GGCN, single-chip and ring sim).
"""

from __future__ import annotations

import dataclasses
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from neutronstarlite_tpu.graph.storage import CSCGraph
from neutronstarlite_tpu.ops.blocked_ell import BlockedEll
from neutronstarlite_tpu.ops.ell import _chunk_budget_bytes
from neutronstarlite_tpu.utils.logging import get_logger

log = get_logger("fused_edge")

# masked-slot score sentinel (bf16-safe, same as ops/ell_gat.NEG_INF);
# exp(NEG_INF - finite) flushes to exactly 0 in f32, never NaN
NEG_INF = -1e30

DEFAULT_FUSED_VT = 4096  # source-tile rows (the resident-slab height)


def default_fused_vt(v_num: int, kernel_tile: int = 0) -> int:
    """KERNEL_TILE when set, else the default slab height capped by V —
    ONE definition shared by the trainers and the benches."""
    return int(kernel_tile) or min(int(v_num), DEFAULT_FUSED_VT)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class FusedEdgePair:
    """Forward (CSC, src-tiled) + transposed (CSR, dst-tiled) unit-weight
    blocked tables. ``fwd`` rows are per-(tile, dst) runs; ``bwd`` rows
    are per-(tile, src) runs — the backward's pass C streams the
    destination side as the resident slab (g, m, l, T1, adst live there)
    while feature/src-half gradients accumulate into the row space."""

    fwd: BlockedEll
    bwd: BlockedEll

    @staticmethod
    def from_host(
        g: CSCGraph, vt: int = 0, levels: str = ""
    ) -> "FusedEdgePair":
        vt = default_fused_vt(g.v_num, vt)
        levels = levels or os.environ.get("NTS_ELL_LEVELS", "") or "binned"
        ones = np.ones(g.e_num, np.float32)
        fwd = BlockedEll.build(
            g.v_num, g.column_offset, g.row_indices, ones, vt, levels=levels
        )
        bwd = BlockedEll.build(
            g.v_num, g.row_offset, g.column_indices, ones, vt, levels=levels
        )
        return FusedEdgePair(fwd=fwd, bwd=bwd)

    def slot_count(self) -> int:
        return sum(int(np.prod(n.shape)) for n in self.fwd.nbr) + sum(
            int(np.prod(n.shape)) for n in self.bwd.nbr
        )


# ---- streamed-pass scaffolding ---------------------------------------------


def _scan_tiles(fe: BlockedEll, per_tile, state, level_fn):
    """Stream the stacked level tables tile by tile, threading ``state``.

    ``per_tile``: tuple of [T, vt, .] arrays resident one tile at a time.
    ``level_fn(state, tile_slices, nbr, msk, dstr) -> state`` runs once
    per level. First tile peeled outside the scan (the blocked_ell
    varying-carry move, so the same body runs inside shard_map)."""
    tables = list(zip(fe.nbr, fe.wgt, fe.dst_row))
    if not tables:
        return state

    def body(state, xs):
        tile_slices, tabs = xs
        for nbr, msk, dstr in tabs:
            state = level_fn(state, tile_slices, nbr, msk, dstr)
        return state, None

    first = (
        tuple(a[0] for a in per_tile),
        [(n[0], w[0], d[0]) for n, w, d in tables],
    )
    state, _ = body(state, first)
    if fe.n_tiles > 1:
        rest = (
            tuple(a[1:] for a in per_tile),
            [(n[1:], w[1:], d[1:]) for n, w, d in tables],
        )
        state, _ = lax.scan(body, state, rest)
    return state


def _scan_row_chunks(state, nbr, msk, dstr, rows, fill, chunk_fn):
    """Byte-bound one level's rows (the [rows, K, max(f, C)] gather slab)
    with an inner scan; first chunk peeled (varying-carry)."""
    n_l, K = nbr.shape
    if n_l <= rows:
        return chunk_fn(state, nbr, msk, dstr)
    n_ch = -(-n_l // rows)
    pad = n_ch * rows - n_l
    nb = jnp.pad(nbr, ((0, pad), (0, 0))).reshape(n_ch, rows, K)
    mk = jnp.pad(msk, ((0, pad), (0, 0))).reshape(n_ch, rows, K)
    dr = jnp.pad(dstr, (0, pad), constant_values=fill).reshape(n_ch, rows)
    state = chunk_fn(state, nb[0], mk[0], dr[0])
    if n_ch > 1:

        def body(s, xs):
            return chunk_fn(s, *xs), None

        state, _ = lax.scan(body, state, (nb[1:], mk[1:], dr[1:]))
    return state


def _tile_stack(fe: BlockedEll, arr: jax.Array) -> jax.Array:
    """[S, .] -> [T, vt, .] resident-slab stacking (pad the tail tile)."""
    S = fe.src_num or fe.v_num
    pad = fe.n_tiles * fe.vt - S
    return jnp.pad(arr, ((0, pad), (0, 0))).reshape(
        fe.n_tiles, fe.vt, arr.shape[1]
    )


def _row_budget(K: int, f: int, C: int) -> int:
    return max(_chunk_budget_bytes() // (K * max(f, C) * 4), 1)


def _scatter_kw():
    return dict(indices_are_sorted=True, unique_indices=True, mode="drop")


# ---- forward: one streamed pass, online softmax ----------------------------


def online_softmax_fold(m_old, l_old, acc_old, z, real, aggregate):
    """One block folded into the running state of an online softmax: the
    ONE definition of the (m, l, acc) recurrence, shared by this module's
    table-driven blocks and ops/causal_attention.py's index-enumerated
    tiles. ``z`` [..., n, K, C] are the block's scores (``NEG_INF`` where
    ``real`` is False), ``m_old`` / ``l_old`` [..., n, C] and ``acc_old``
    [..., n, f] the state of its n destination rows, ``aggregate(p)`` the
    block's values weighted by ``p`` [..., n, K, C] -> [..., n, f]. The
    carried state is rescaled by exp(m_old - m_new) (all-padding rows:
    exp(0) = 1) and the block's exp-scores folded in."""
    m_new = jnp.maximum(m_old, z.max(axis=-2))  # block max per destination row
    p = jnp.where(real, jnp.exp(z - m_new[..., None, :]), 0.0)
    scale = jnp.exp(m_old - m_new)
    return m_new, l_old * scale + p.sum(axis=-2), acc_old * scale + aggregate(p)


def fused_init_state(v_num: int, C: int, f: int):
    """(m, l, acc) — running per-destination max / normalizer / weighted
    accumulator. The distributed ring carries this tuple across hops."""
    return (
        jnp.full((v_num, C), NEG_INF, jnp.float32),
        jnp.zeros((v_num, C), jnp.float32),
        jnp.zeros((v_num, f), jnp.float32),
    )


def fused_forward_into(
    fe: BlockedEll, state, h, asrc, adst, slope: float
):
    """Fold one table set's contributions into the carried (m, l, acc).

    ``h`` [S, f] / ``asrc`` [S, C] live in the table's SOURCE space (one
    ring shard on the dist path); ``adst`` [V, C] in its destination
    space. Per block: scores from the resident slabs, block max, rescale
    the carried state by exp(m_old - m_new), fold exp-scores and weighted
    features in — the flash-attention update over graph runs."""
    V, f, C = fe.v_num, h.shape[1], asrc.shape[1]
    ht = _tile_stack(fe, h)
    at = _tile_stack(fe, asrc)
    ad = adst.astype(jnp.float32)

    def level_fn(state, tile, nbr, msk, dstr):
        x_tile, a_tile = tile
        rows = _row_budget(nbr.shape[1], f, C)

        def chunk_fn(state, nb, mk, dr):
            m, l, acc = state
            drc = jnp.minimum(dr, V - 1)  # clamp padding rows (dropped below)
            real = (mk != 0.0)[:, :, None]
            q = a_tile[nb].astype(jnp.float32) + ad[drc][:, None, :]
            z = jnp.where(
                real, jax.nn.leaky_relu(q, negative_slope=slope), NEG_INF
            )
            xv = x_tile[nb].astype(jnp.float32)  # [n, K, f]
            m_new, l_new, acc_new = online_softmax_fold(
                m[drc], l[drc], acc[drc], z, real,
                lambda p: (xv * p).sum(axis=1),  # C==1 broadcasts over f
            )
            kw = _scatter_kw()
            return (
                m.at[dr].set(m_new, **kw),
                l.at[dr].set(l_new, **kw),
                acc.at[dr].set(acc_new, **kw),
            )

        return _scan_row_chunks(state, nbr, msk, dstr, rows, V, chunk_fn)

    return _scan_tiles(fe, (ht, at), state, level_fn)


def fused_finalize(state, dtype):
    """acc / l with the empty-destination zero convention (the pinned
    ops/edge.edge_softmax behavior: no in-edges -> exact zeros)."""
    _, l, acc = state
    return jnp.where(l > 0, acc / jnp.where(l > 0, l, 1.0), 0.0).astype(dtype)


# ---- backward: three streamed passes ---------------------------------------


def _safe_l(l):
    return jnp.where(l > 0, l, 1.0)


def _recompute_s(a_tile, nb, ad_rows, m_rows, l_rows, real, slope):
    """Blockwise softmax recomputation from the saved (m, l) statistics:
    s = exp(leaky_relu(q) - m[dst]) / l[dst], zero on padding slots."""
    q = a_tile[nb].astype(jnp.float32) + ad_rows[:, None, :]
    z = jax.nn.leaky_relu(q, negative_slope=slope)
    s = jnp.where(
        real, jnp.exp(z - m_rows[:, None, :]) / l_rows[:, None, :], 0.0
    )
    return q, s


def _score_grad(s, gs, t1_b, q, real, slope):
    """Softmax Jacobian s*(gs - T1[dst]) through the leaky_relu: the
    reference backward s*(g - sum_dst(s*g)) recomputed blockwise.
    ``t1_b`` is already broadcastable to ``gs`` ([n, 1, C] from a row
    gather in pass B, [n, K, C] from a slot gather in pass C)."""
    dq = jnp.where(q >= 0, 1.0, slope)
    return jnp.where(real, s * (gs - t1_b) * dq, 0.0)


def fused_bwd_t1_into(fe: BlockedEll, t1, h, asrc, adst, m, l, g, slope):
    """Pass A (forward tables): T1[d] = sum over in-edges of s * gs where
    gs is the per-edge score cotangent <g[d], h[src]> (summed over f for
    C==1, per-channel for C==f) — the per-destination Jacobian sum the
    blockwise softmax backward needs complete before pass B/C."""
    V, f, C = fe.v_num, h.shape[1], asrc.shape[1]
    ht = _tile_stack(fe, h)
    at = _tile_stack(fe, asrc)
    ad, gf = adst.astype(jnp.float32), g.astype(jnp.float32)
    ls = _safe_l(l)

    def level_fn(t1, tile, nbr, msk, dstr):
        x_tile, a_tile = tile
        rows = _row_budget(nbr.shape[1], f, C)

        def chunk_fn(t1, nb, mk, dr):
            drc = jnp.minimum(dr, V - 1)
            real = (mk != 0.0)[:, :, None]
            _, s = _recompute_s(
                a_tile, nb, ad[drc], m[drc], ls[drc], real, slope
            )
            xv = x_tile[nb].astype(jnp.float32)
            gs = gf[drc][:, None, :] * xv
            if C == 1:
                gs = gs.sum(axis=2, keepdims=True)
            return t1.at[dr].add((s * gs).sum(axis=1), **_scatter_kw())

        return _scan_row_chunks(t1, nbr, msk, dstr, rows, V, chunk_fn)

    return _scan_tiles(fe, (ht, at), t1, level_fn)


def fused_bwd_gadst_into(
    fe: BlockedEll, gad, h, asrc, adst, m, l, t1, g, slope
):
    """Pass B (forward tables, T1 complete): per-destination score-half
    gradient grad_adst[d] = sum over in-edges of gz (rows are unique
    destinations per tile, so the scatter stays sorted+unique)."""
    V, f, C = fe.v_num, h.shape[1], asrc.shape[1]
    ht = _tile_stack(fe, h)
    at = _tile_stack(fe, asrc)
    ad, gf = adst.astype(jnp.float32), g.astype(jnp.float32)
    ls = _safe_l(l)

    def level_fn(gad, tile, nbr, msk, dstr):
        x_tile, a_tile = tile
        rows = _row_budget(nbr.shape[1], f, C)

        def chunk_fn(gad, nb, mk, dr):
            drc = jnp.minimum(dr, V - 1)
            real = (mk != 0.0)[:, :, None]
            q, s = _recompute_s(
                a_tile, nb, ad[drc], m[drc], ls[drc], real, slope
            )
            xv = x_tile[nb].astype(jnp.float32)
            gs = gf[drc][:, None, :] * xv
            if C == 1:
                gs = gs.sum(axis=2, keepdims=True)
            gz = _score_grad(s, gs, t1[drc][:, None, :], q, real, slope)
            return gad.at[dr].add(gz.sum(axis=1), **_scatter_kw())

        return _scan_row_chunks(gad, nbr, msk, dstr, rows, V, chunk_fn)

    return _scan_tiles(fe, (ht, at), gad, level_fn)


def fused_bwd_src_into(
    feT: BlockedEll, state, h, asrc, adst, m, l, t1, g, slope
):
    """Pass C (TRANSPOSED tables, tiled by destination): stream the
    destination side as the resident slab (adst, m, l, T1, g) and
    accumulate the source-space gradients — grad_h[src] += s * g[dst]
    (the value path) and grad_asrc[src] += gz (the score path). Rows are
    unique SOURCES per tile, so both scatters stay sorted+unique. On the
    dist path the resident slab is the reverse-ring payload and
    (grad_h, grad_asrc) stay device-local."""
    S = feT.v_num  # the transposed table's row space = source vertices
    f, C = h.shape[1], asrc.shape[1]
    adt = _tile_stack(feT, adst.astype(jnp.float32))
    mt = _tile_stack(feT, m)
    lt = _tile_stack(feT, _safe_l(l))
    t1t = _tile_stack(feT, t1)
    gt = _tile_stack(feT, g.astype(jnp.float32))
    hf, af = h.astype(jnp.float32), asrc.astype(jnp.float32)

    def level_fn(state, tile, nbr, msk, dstr):
        ad_t, m_t, l_t, t1_t, g_t = tile
        rows = _row_budget(nbr.shape[1], f, C)

        def chunk_fn(state, nb, mk, dr):
            gh, gas = state
            drc = jnp.minimum(dr, S - 1)  # rows are SOURCE vertices here
            real = (mk != 0.0)[:, :, None]
            q = af[drc][:, None, :] + ad_t[nb].astype(jnp.float32)
            z = jax.nn.leaky_relu(q, negative_slope=slope)
            s = jnp.where(real, jnp.exp(z - m_t[nb]) / l_t[nb], 0.0)
            gv = g_t[nb]  # [n, K, f] resident-gathered cotangent rows
            gh_row = (s * gv).sum(axis=1)  # value-path feature gradient
            gs = gv * hf[drc][:, None, :]
            if C == 1:
                gs = gs.sum(axis=2, keepdims=True)
            gz = _score_grad(s, gs, t1_t[nb], q, real, slope)
            kw = _scatter_kw()
            return (
                gh.at[dr].add(gh_row, **kw),
                gas.at[dr].add(gz.sum(axis=1), **kw),
            )

        return _scan_row_chunks(state, nbr, msk, dstr, rows, S, chunk_fn)

    return _scan_tiles(feT, (adt, mt, lt, t1t, gt), state, level_fn)


# ---- the custom_vjp-paired single-chip op ----------------------------------


def _fused_forward(fe: BlockedEll, h, asrc, adst, slope):
    state = fused_init_state(fe.v_num, asrc.shape[1], h.shape[1])
    m, l, acc = fused_forward_into(fe, state, h, asrc, adst, slope)
    return fused_finalize((m, l, acc), h.dtype), (m, l)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _fused_apply(slope, pair, h, asrc, adst):
    out, _ = _fused_forward(pair.fwd, h, asrc, adst, slope)
    return out


def _fused_apply_fwd(slope, pair, h, asrc, adst):
    out, (m, l) = _fused_forward(pair.fwd, h, asrc, adst, slope)
    return out, (pair, h, asrc, adst, m, l)


def _fused_apply_bwd(slope, res, g):
    from neutronstarlite_tpu.ops.segment import zero_cotangent

    pair, h, asrc, adst, m, l = res
    f, C = h.shape[1], asrc.shape[1]
    V = pair.fwd.v_num  # destination space
    S = pair.bwd.v_num  # source space (== V on the square single-chip form)
    t1 = fused_bwd_t1_into(
        pair.fwd, jnp.zeros((V, C), jnp.float32), h, asrc, adst, m, l, g,
        slope,
    )
    gad = fused_bwd_gadst_into(
        pair.fwd, jnp.zeros((V, C), jnp.float32), h, asrc, adst, m, l, t1,
        g, slope,
    )
    gh, gas = fused_bwd_src_into(
        pair.bwd,
        (jnp.zeros((S, f), jnp.float32), jnp.zeros((S, C), jnp.float32)),
        h, asrc, adst, m, l, t1, g, slope,
    )
    return (
        jax.tree.map(zero_cotangent, pair),
        gh.astype(h.dtype),
        gas.astype(asrc.dtype),
        gad.astype(adst.dtype),
    )


_fused_apply.defvjp(_fused_apply_fwd, _fused_apply_bwd)


def fused_edge_attention_aggregate(
    pair: FusedEdgePair,
    h: jax.Array,
    asrc: jax.Array,
    adst: jax.Array,
    slope: float,
) -> jax.Array:
    """The whole score -> per-dst softmax -> weighted-aggregate chain,
    [V, f] -> [V, f], no [Ep, .] tensors. ``asrc``/``adst`` [V, C] are the
    decomposed score halves (C=1: GAT scalar attention; C=f: GGCN
    per-channel gates); gradients flow to all three inputs."""
    return _fused_apply(float(slope), pair, h, asrc, adst)
