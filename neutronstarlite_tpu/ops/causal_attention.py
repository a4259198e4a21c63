"""Edge attention over the implicit causal graph of a token sequence.

The graph: vertices are the positions ``0 .. S-1`` of one sequence, vertex
``i`` has an in-edge from every ``j <= i``, and no table holds them. The
operator is the chain ops/edge.py names (a score per edge, a softmax per
destination, a weighted aggregate of the sources) with a ``q . k`` score
and a leading axis of independent (sequence, head) pairs, streamed as
ops/fused_edge.py streams it: the (m, l, acc) state of every destination
is carried across source tiles and no ``[S, S]`` value ever exists (16
heads x 8,192 x 8,192 float32 scores would be 4.3 GB a sequence).

Where fused_edge.py walks tables of (src-tile, dst-run) blocks, the tiles
here are enumerated by index arithmetic: with ``b`` positions a block,
destination block ``i`` takes the source blocks ``j = 0 .. i`` and no
other (a loop whose bound is ``i + 1``); only the diagonal tile ``j == i``
holds non-edges, masked by position. A destination block's (m, l, acc) is
the carry of its inner loop and is written once, when its last source tile
has been folded in: a first form that kept the state of all destinations
in one array and updated a slice of it per tile spent 29% of the step in
those updates (my chip run, PR 28).

Shared with fused_edge.py, one definition each: the recurrence
(``online_softmax_fold``), the finalize (``fused_finalize``: acc / l, a
destination without in-edges gives zeros) and the masked-score sentinel.
Not shared: the backward. fused_edge's recomputes ``s`` through its
leaky-relu score and walks transposed tables in three passes; a dot-product
score over an index-enumerated triangle needs no table (a tile's transpose
is the same tile), so the flash-attention pairing is written here, in two
passes that each write their results once: by destination block for the
query gradients (source blocks ``0 .. i``), by source block for the key
and value gradients (destination blocks ``j .. nb - 1``), ``p = exp(s -
lse)`` recomputed from the saved log-normaliser in both, ``ds = p (dp -
sum(out * d_out))``. One pass with read-modify-write accumulators makes
five products a tile to these seven and measured 1.6 to 2.7 times slower
on the v5e (PERF.md section 6, PR 28).

**Grouped queries** (``group`` > 1): ``group`` query heads share one key and
value head. ``q`` then has ``group`` rows for each row of ``k`` and ``v``
(row ``n * group + g`` of ``q`` attends row ``n`` of ``k``), and a tile's
queries are the ``group * b`` rows of all the group's heads at one
destination block, laid one head after the other (a reshape of the rows
read: nothing is copied), its mask the position of a row within its own
head. So one key/value tile is read once for the whole group, forward and
in both backward passes, no ``[N * group, S, d]`` copy of K or V exists,
and the key and value gradients' sum over the group is the sum over a
tile's query rows that the products ``ds^T q`` and ``p^T g`` make anyway:
``dK`` and ``dV`` are written once, already summed. ``group = 1`` is the
code as it was, instruction for instruction.

Numeric policy as blocked_ell / fused_edge: the products read their
operands in the dtype given (bfloat16 under PRECISION:bfloat16), scores,
state and accumulators are float32, one cast at the end.

**Two forms of the walk.** On a TPU, where the block is a multiple of
``LANES`` and one key head's float32 key and value gradients fit
``KEPT_BYTES`` (``kernel_takes``), the forward and the backward are each
one Pallas kernel, ``causal_attention`` and ``causal_attention_backward``
(``jax.custom_vjp`` as before); elsewhere they are the ``fori_loop`` walk
above, unchanged (``lax.platform_dependent``: chosen when the program is
lowered), which is what tier-1 compares the kernels with (Pallas' interpret
mode there). The kernels keep the walk's tiles, its order of folds and its
numeric policy; what moves is where a tile's values live: scores, ``p``,
``dp``, ``ds`` and every accumulator stay in VMEM and none passes through
HBM.

- The grid visits the causal tiles alone: ``_steps`` lists them in order as
  prefetched scalars that the blocks' index maps read, so no grid step and
  no copy exists for a tile above the diagonal; the diagonal tile, the only
  one masked, is traced apart.
- The layout is the walk's transposed: a key a sublane, a query row a lane.
  A tile's scores ``[1, b, rows]`` are then ``online_softmax_fold``'s ``[n,
  K, C]``, so the fold and ``fused_finalize`` are called as they are (the
  state a row ``[1, rows]``, m and l, and ``[dv, rows]``, acc), and the
  log-normaliser leaves the kernel as a row along the lanes, ``[N * group,
  1, S]`` (a trailing axis of 1 on the lanes would take 128 times its
  bytes), reshaped to the walk's ``[N * group, S, 1]`` for the residuals,
  so the two forms save the same arrays.
- A grid step holds ``tile_heads`` query heads, a part of one key head's
  group or the whole groups of several key heads, as many as keep its
  float32 ``[rows, b]`` scores and ``[rows, dk + dv]`` rows within
  ``TILE_BYTES`` and its key heads' kept gradients within ``KEPT_BYTES``: a
  rule on the shapes, no option. A step of several key heads folds each
  one's tile in turn.
- The backward is one pass, by destination block: a destination's query
  gradients accumulate ``[dk, rows]`` over its tiles and are written when
  the last is in; every source block's key and value gradients accumulate
  in VMEM for the whole sequence (``[blocks, b, d]`` a key head) from the
  first tile that reads it, and are written during the last destination
  block, summed over the group. That is five products a tile and one
  recomputation of ``p``, where the walk's two passes make seven and two;
  with nothing read back from HBM the one pass is the faster on the chip
  (PERF.md section 6).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from neutronstarlite_tpu.ops.delta_rule import compiler_params
from neutronstarlite_tpu.ops.fused_edge import NEG_INF, fused_finalize, online_softmax_fold

DEFAULT_BLOCK = 512
LANES = 128  # the kernels take a block in multiples of it: a head's positions lie along the lanes
TILE_BYTES = 8 * 2 ** 20  # float32 bytes of a grid step's [rows, block] scores and [rows, dk + dv] rows, at most
KEPT_BYTES = 48 * 2 ** 20  # float32 bytes of key and value gradients the backward keeps for whole sequences, at most


def _rows(x, block_index, block: int):
    return lax.dynamic_slice_in_dim(x, block_index * block, block, axis=1)


def _put(x, rows, block_index, block: int):
    return lax.dynamic_update_slice_in_dim(x, rows, block_index * block, axis=1)


def _dot(spec: str, a, b):
    precision = lax.Precision.HIGHEST if a.dtype == jnp.float32 else None
    return jnp.einsum(spec, a, b, precision=precision, preferred_element_type=jnp.float32)


def _grouped(rows, group: int):
    """[N * group, b, d] -> [N, group * b, d]: a group's heads one after
    the other."""
    return rows.reshape(rows.shape[0] // group, group * rows.shape[1], *rows.shape[2:])


def _ungrouped(rows, group: int):
    return rows.reshape(rows.shape[0] * group, rows.shape[1] // group, *rows.shape[2:])


def _tile_scores(q_i, k_j, i, j, scale: float, block: int):
    """(masked scores [N, group * b, b] float32, the edge mask) of one
    tile; a query row's position is its place within its own head."""
    z = _dot("nqd,nkd->nqk", q_i, k_j) * scale
    rows, first = q_i.shape[1], i * block
    within = lax.broadcasted_iota(jnp.int32, (rows, block), 0)
    dst = first + (within if rows == block else within % block)
    src = j * block + lax.broadcasted_iota(jnp.int32, (rows, block), 1)
    real = (src <= dst)[None]
    return jnp.where(real, z, NEG_INF), real


def _forward(q, k, v, scale: float, block: int, group: int):
    """(out [N * group, S, dv] in ``v``'s dtype, lse [N * group, S, 1]
    float32)."""
    n, s, dv = v.shape
    rows = group * block

    def dst_block(i, carry):
        out, lse = carry
        q_i = _grouped(_rows(q, i, block), group)
        state = (
            jnp.full((n, rows, 1), NEG_INF, jnp.float32),
            jnp.zeros((n, rows, 1), jnp.float32),
            jnp.zeros((n, rows, dv), jnp.float32),
        )

        def src_block(j, state):
            v_j = _rows(v, j, block)
            z, real = _tile_scores(q_i, _rows(k, j, block), i, j, scale, block)
            return online_softmax_fold(
                *state, z[..., None], real[..., None],
                lambda p: _dot("nqk,nkd->nqd", p[..., 0].astype(v.dtype), v_j),
            )

        m, l, acc = lax.fori_loop(0, i + 1, src_block, state)
        out = _put(out, _ungrouped(fused_finalize((m, l, acc), v.dtype), group), i, block)
        return out, _put(lse, _ungrouped(m + jnp.log(l), group), i, block)

    init = (jnp.zeros((n * group, s, dv), v.dtype), jnp.zeros((n * group, s, 1), jnp.float32))
    return lax.fori_loop(0, s // block, dst_block, init)


def _tile_grads(q_i, k_j, v_j, g_i, lse_i, delta_i, i, j, scale: float, block: int):
    """(p, ds) of one tile, recomputed from the saved log-normaliser."""
    z, real = _tile_scores(q_i, k_j, i, j, scale, block)
    p = jnp.where(real, jnp.exp(z - lse_i), 0.0)
    dp = _dot("nqd,nkd->nqk", g_i, v_j)
    return p.astype(v_j.dtype), (p * (dp - delta_i) * scale).astype(q_i.dtype)


def _cotangent(out, g, dtype):
    """(``g`` in the values' dtype, ``delta = sum(out * g)`` a row [N *
    group, S, 1] float32)."""
    g = g.astype(dtype)
    return g, jnp.sum(out.astype(jnp.float32) * g.astype(jnp.float32), axis=-1, keepdims=True)


def _attention_bwd(scale, block, group, res, g):
    q, k, v, out, lse = res
    n, s, dk = k.shape
    nb = s // block
    g, delta = _cotangent(out, g, v.dtype)

    def rows_of(i):
        return tuple(_grouped(_rows(a, i, block), group) for a in (q, g, lse, delta))

    def query_block(i, dq):  # destination block i over its source blocks 0 .. i
        q_i, g_i, lse_i, delta_i = rows_of(i)

        def src_block(j, acc):
            k_j = _rows(k, j, block)
            _, ds = _tile_grads(q_i, k_j, _rows(v, j, block), g_i, lse_i, delta_i, i, j, scale, block)
            return acc + _dot("nqk,nkd->nqd", ds, k_j)

        acc = lax.fori_loop(0, i + 1, src_block, jnp.zeros((n, group * block, dk), jnp.float32))
        return _put(dq, _ungrouped(acc.astype(q.dtype), group), i, block)

    def key_block(j, carry):  # source block j over its destination blocks j .. nb - 1
        d_k, d_v = carry
        k_j, v_j = _rows(k, j, block), _rows(v, j, block)

        def dst_block(i, acc):
            q_i, g_i, lse_i, delta_i = rows_of(i)
            p, ds = _tile_grads(q_i, k_j, v_j, g_i, lse_i, delta_i, i, j, scale, block)
            return acc[0] + _dot("nqk,nqd->nkd", ds, q_i), acc[1] + _dot("nqk,nqd->nkd", p, g_i)

        zeros = (jnp.zeros((n, block, dk), jnp.float32), jnp.zeros((n, block, v.shape[-1]), jnp.float32))
        dk_j, dv_j = lax.fori_loop(j, nb, dst_block, zeros)
        return _put(d_k, dk_j.astype(k.dtype), j, block), _put(d_v, dv_j.astype(v.dtype), j, block)

    dq = lax.fori_loop(0, nb, query_block, jnp.zeros(q.shape, q.dtype))
    d_k, d_v = lax.fori_loop(0, nb, key_block, (jnp.zeros(k.shape, k.dtype), jnp.zeros(v.shape, v.dtype)))
    return dq, d_k, d_v


# ---- the kernels: the same walk with a tile's every value in VMEM

NT, NN, TN = ((1,), (1,)), ((1,), (0,)), ((0,), (0,))  # contracted axes (a's, b's) of a tile's products


def _mm(a, b, axes):
    precision = lax.Precision.HIGHEST if a.dtype == jnp.float32 else None
    return lax.dot_general(a, b, (axes, ((), ())), precision=precision, preferred_element_type=jnp.float32)


def tile_heads(key_heads: int, group: int, positions: int, block: int, dk: int, dv: int) -> int:
    """Query heads a grid step of the kernels holds: a part of one key
    head's group (a divisor of it) or the whole groups of several key heads
    (a multiple of it), the most whose float32 ``[rows, block]`` scores and
    ``[rows, dk + dv]`` rows fit ``TILE_BYTES`` and whose key heads' kept
    gradients fit ``KEPT_BYTES``; one at least."""
    heads = key_heads * group

    def fits(h):
        return (h * block * (block + dk + dv) * 4 <= TILE_BYTES
                and max(1, h // group) * positions * (dk + dv) * 4 <= KEPT_BYTES)

    return max([h for h in range(1, heads + 1)
                if heads % h == 0 and (group % h == 0 or h % group == 0) and fits(h)], default=1)


def rows_per_tile(key_heads: int, group: int, positions: int, block: int, dk: int, dv: int) -> int:
    """Query rows a grid step of the kernels holds."""
    return tile_heads(key_heads, group, positions, block, dk, dv) * block


def kernel_takes(positions: int, block: int, dk: int, dv: int) -> bool:
    """Whether the kernels take a sequence of ``positions`` in tiles of
    ``block``: whole tiles, a block in multiples of ``LANES``, one key
    head's float32 key and value gradients within ``KEPT_BYTES``."""
    return block % LANES == 0 and positions % block == 0 and positions * (dk + dv) * 4 <= KEPT_BYTES


def resolve_block(positions: int, block: int = 0) -> int:
    """Positions a tile: ``block``, or ``DEFAULT_BLOCK`` capped by the
    sequence."""
    return int(block) or min(DEFAULT_BLOCK, positions)


def _query_rows(ref, h: int, per: int):
    """Key head ``h``'s ``per`` query heads of a step's ``[heads, b, d]``
    block (queries or their cotangents) as the ``[per * b, d]`` rows of its
    tile, one head after the other (``_grouped``'s order)."""
    _, b, d = ref.shape
    return ref[h * per: (h + 1) * per].reshape(per * b, d)


def _row_stats(ref, h: int, per: int):
    """Key head ``h``'s rows of a ``[heads, 1, b]`` block of a float32 a
    query row (lse, delta), as one row ``[1, per * b]`` along the lanes."""
    return jnp.concatenate([ref[x] for x in range(h * per, (h + 1) * per)], axis=1)


def _scores_t(q, k, scale: float, diagonal: bool):
    """(masked scores ``[b, rows]`` float32 of one tile, its edge mask): the
    transpose of ``_tile_scores``', a key a sublane and a query row a lane;
    off the diagonal every pair is an edge and nothing is masked."""
    z = _mm(k, q, NT) * scale
    if not diagonal:
        return z, True
    b, rows = z.shape
    src = lax.broadcasted_iota(jnp.int32, (b, rows), 0)
    dst = lax.broadcasted_iota(jnp.int32, (b, rows), 1)
    real = src <= (dst if rows == b else lax.rem(dst, b))
    return jnp.where(real, z, NEG_INF), real


def _grads_t(q, k, v, g, lse, delta, scale: float, diagonal: bool):
    """``_tile_grads`` of one tile, transposed: (p, ds) ``[b, rows]``."""
    z, real = _scores_t(q, k, scale, diagonal)
    p = jnp.where(real, jnp.exp(z - lse), 0.0)
    dp = _mm(v, g, NT)
    return p.astype(v.dtype), (p * (dp - delta) * scale).astype(q.dtype)


def _each_tile(i_ref, j_ref, k_ref, q_ref, body):
    """``body(diagonal, h, per, c)`` for each key head ``h`` of the grid
    step, ``per`` query heads of its own, its query rows at lanes ``c`` of
    the step's; the diagonal tile (the only one masked) traced apart."""
    keys, block = k_ref.shape[:2]
    per = q_ref.shape[0] // keys

    def each(diagonal):
        for h in range(keys):
            body(diagonal, h, per, slice(h * per * block, (h + 1) * per * block))

    t = pl.program_id(1)
    pl.when(i_ref[t] != j_ref[t])(lambda: each(False))
    pl.when(i_ref[t] == j_ref[t])(lambda: each(True))


def _forward_kernel(i_ref, j_ref, s_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref, *, scale):
    """Destination block ``i``'s tiles ``j = 0 .. i`` folded into its (m, l,
    acc) in VMEM by ``online_softmax_fold``, in the transposed layout: a
    tile's scores ``[1, b, rows]`` are the fold's ``[n, K, C]``, a key a
    sublane and a query row a lane, so the state is a row ``[1, rows]`` (m,
    l) and ``[dv, rows]`` (acc) and the log-normaliser leaves as a row."""
    t = pl.program_id(1)
    block = k_ref.shape[1]

    @pl.when(j_ref[t] == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def fold(diagonal, h, per, c):
        z, real = _scores_t(_query_rows(q_ref, h, per), k_ref[h], scale, diagonal)
        v = v_ref[h]
        m_ref[:, c], l_ref[:, c], acc_ref[:, c] = online_softmax_fold(
            m_ref[:, c], l_ref[:, c], acc_ref[:, c], z[None], real[None] if diagonal else True,
            lambda p: _mm(v, p[0].astype(v.dtype), TN))

    _each_tile(i_ref, j_ref, k_ref, q_ref, fold)

    @pl.when(j_ref[t] == i_ref[t])
    def _():
        out = fused_finalize((m_ref[...], l_ref[...], acc_ref[...]), jnp.float32)
        o_ref[...] = out.T.reshape(o_ref.shape).astype(o_ref.dtype)
        for x in range(lse_ref.shape[0]):
            c = slice(x * block, (x + 1) * block)
            lse_ref[x] = m_ref[:, c] + jnp.log(l_ref[:, c])


def _backward_kernel(i_ref, j_ref, s_ref, q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref, dq_ref, dk_ref, dv_ref,
                     dq_acc, dk_acc, dv_acc, *, scale, last, parts):
    """All three gradients in one walk by destination block: ``i``'s query
    gradients over its tiles ``j = 0 .. i`` (and each part of the group)
    accumulated ``[dk, rows]`` and written when its last tile is in; every
    source block's key and value gradients accumulated ``[blocks, b, d]`` a
    key head in VMEM from the first tile that reads it (``i = j``, the first
    part) and written during the last destination block, summed over the
    group as they are written."""
    t = pl.program_id(1)
    i, j, s = i_ref[t], j_ref[t], s_ref[t]

    @pl.when(j == 0)
    def _():
        dq_acc[...] = jnp.zeros(dq_acc.shape, jnp.float32)

    @pl.when((i == j) & (s == 0))
    def _():
        dk_acc[:, j] = jnp.zeros(dk_acc.shape[:1] + dk_acc.shape[2:], jnp.float32)
        dv_acc[:, j] = jnp.zeros(dv_acc.shape[:1] + dv_acc.shape[2:], jnp.float32)

    def fold(diagonal, h, per, c):
        q, k, g = _query_rows(q_ref, h, per), k_ref[h], _query_rows(g_ref, h, per)
        p, ds = _grads_t(q, k, v_ref[h], g, _row_stats(lse_ref, h, per), _row_stats(delta_ref, h, per),
                         scale, diagonal)
        dq_acc[:, c] += _mm(k, ds, TN)
        dk_acc[h, j] += _mm(ds, q, NN)
        dv_acc[h, j] += _mm(p, g, NN)

    _each_tile(i_ref, j_ref, k_ref, q_ref, fold)

    @pl.when(j == i)
    def _():
        dq_ref[...] = dq_acc[...].T.reshape(dq_ref.shape).astype(dq_ref.dtype)

    @pl.when((i == last) & (s == parts - 1))
    def _():
        dk_ref[...] = dk_acc[:, j].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[:, j].astype(dv_ref.dtype)


def _steps(blocks: int, parts: int):
    """The causal tiles a kernel visits, in its order, as three int32 arrays
    (destination block ``i``, part ``s`` of the group, source block ``j``):
    by destination block, each part of the group, ``i``'s sources ``0 ..
    i``. No grid step exists for a tile above the diagonal."""
    steps = [(i, j, s) for i in range(blocks) for s in range(parts) for j in range(i + 1)]
    return tuple(jnp.asarray(np.asarray(column, np.int32)) for column in zip(*steps))


def _specs(heads: int, keys: int, parts: int, block: int, last: int):
    """Block specs of a kernel's operands by kind, for a grid over (a step's
    ``keys`` key/value heads, beside their ``heads`` query heads when the
    step holds whole groups; the tiles of ``_steps``). A key head's
    gradients (``"written"``) stay on block 0 until the last destination
    block writes them, so no other block is written back before."""
    row = lambda r, t, s: r * parts + s[t]  # noqa: E731
    return {
        "query": lambda d: pl.BlockSpec((heads, block, d), lambda r, t, i, j, s: (row(r, t, s), i[t], 0)),
        "stat": pl.BlockSpec((heads, 1, block), lambda r, t, i, j, s: (row(r, t, s), 0, i[t])),
        "key": lambda d: pl.BlockSpec((keys, block, d), lambda r, t, i, j, s: (r, j[t], 0)),
        "written": lambda d: pl.BlockSpec((keys, block, d), lambda r, t, i, j, s: (
            r, jnp.where((i[t] == last) & (s[t] == parts - 1), j[t], 0), 0)),
    }


def _layout(k, v, block: int, group: int):
    """(query heads, key heads, parts of a group) a grid step holds."""
    heads = tile_heads(k.shape[0], group, k.shape[1], block, k.shape[-1], v.shape[-1])
    return heads, max(1, heads // group), max(1, group // heads)


def _call(kernel, name: str, grid, specs, out_shape, scratch, steps, operands, interpret: bool):
    in_specs, out_specs = specs
    return pl.pallas_call(
        kernel, out_shape=out_shape, interpret=interpret, name=name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=grid, in_specs=in_specs, out_specs=out_specs, scratch_shapes=scratch),
        **compiler_params(interpret))(*steps, *operands)


def _kernel_forward(q, k, v, scale: float, block: int, group: int, interpret: bool):
    """``_forward`` as one kernel; its log-normaliser leaves the kernel as
    a row a query head, ``[N * group, 1, S]``, and is saved as the walk
    saves it."""
    rows, s, dk = q.shape
    dv = v.shape[-1]
    heads, keys, parts = _layout(k, v, block, group)
    steps = _steps(s // block, parts)
    sp = _specs(heads, keys, parts, block, s // block - 1)
    tile = heads * block
    out, lse = _call(
        functools.partial(_forward_kernel, scale=scale), "causal_attention", (k.shape[0] // keys, len(steps[0])),
        ([sp["query"](dk), sp["key"](dk), sp["key"](dv)], [sp["query"](dv), sp["stat"]]),
        [jax.ShapeDtypeStruct((rows, s, dv), v.dtype), jax.ShapeDtypeStruct((rows, 1, s), jnp.float32)],
        [pltpu.VMEM((1, tile), jnp.float32), pltpu.VMEM((1, tile), jnp.float32), pltpu.VMEM((dv, tile), jnp.float32)],
        steps, (q, k, v), interpret)
    return out, lse.reshape(rows, s, 1)


def _kernel_backward(q, k, v, out, lse, g, scale: float, block: int, group: int, interpret: bool):
    """``_attention_bwd``'s two passes as one kernel (``_backward_kernel``)."""
    g, delta = _cotangent(out, g, v.dtype)
    n, s, dk = k.shape
    dv = v.shape[-1]
    heads, keys, parts = _layout(k, v, block, group)
    blocks = s // block
    steps = _steps(blocks, parts)
    sp = _specs(heads, keys, parts, block, blocks - 1)
    return _call(
        functools.partial(_backward_kernel, scale=scale, last=blocks - 1, parts=parts), "causal_attention_backward",
        (n // keys, len(steps[0])),
        ([sp["query"](dk), sp["key"](dk), sp["key"](dv), sp["query"](dv), sp["stat"], sp["stat"]],
         [sp["query"](dk), sp["written"](dk), sp["written"](dv)]),
        [jax.ShapeDtypeStruct(t.shape, t.dtype) for t in (q, k, v)],
        [pltpu.VMEM((dk, heads * block), jnp.float32), pltpu.VMEM((keys, blocks, block, dk), jnp.float32),
         pltpu.VMEM((keys, blocks, block, dv), jnp.float32)],
        steps, (q, k, v, g, *(a.reshape(q.shape[0], 1, s) for a in (lse, delta))), interpret)


def _form(walk, kernel, form, operands, block: int, **static):
    """One of the two forms of a pass: ``form`` "walk", "interpret" (the
    kernel in Pallas' interpret mode), or None: the kernel on a TPU where it
    takes the tiles, else the walk (chosen when the program is lowered)."""
    walk = functools.partial(walk, block=block, **static)
    q, v = operands[0], operands[2]
    if form == "walk" or (form is None and not kernel_takes(q.shape[1], block, q.shape[-1], v.shape[-1])):
        return walk(*operands)
    kernel = functools.partial(kernel, block=block, interpret=form == "interpret", **static)
    if form == "interpret":
        return kernel(*operands)
    return lax.platform_dependent(*operands, tpu=kernel, default=walk)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def attention(q, k, v, scale, block, group, form=None):
    """``causal_edge_attention`` of a resolved block, in the form ``form``
    (``_form``)."""
    return _attention_fwd(q, k, v, scale, block, group, form)[0]


def _attention_fwd(q, k, v, scale, block, group, form):
    out, lse = _form(_forward, _kernel_forward, form, (q, k, v), block, scale=scale, group=group)
    return out, (q, k, v, out, lse)


def _walk_backward(q, k, v, out, lse, g, scale, block, group):
    return _attention_bwd(scale, block, group, (q, k, v, out, lse), g)


def _backward(scale, block, group, form, res, g):
    return _form(_walk_backward, _kernel_backward, form, (*res, g), block, scale=scale, group=group)


attention.defvjp(_attention_fwd, _backward)


def causal_edge_attention(q: jax.Array, k: jax.Array, v: jax.Array, scale: float,
                          block: int = 0, group: int = 1) -> jax.Array:
    """``q`` [N * group, S, dk], ``k`` [N, S, dk], ``v`` [N, S, dv] -> [N *
    group, S, dv]: for each of the N independent (sequence, key/value head)
    pairs and each of the ``group`` query heads that share it (rows ``n *
    group ..`` of ``q``), destination ``i`` aggregates ``v[j]`` over ``j <=
    i`` weighted by ``softmax_j(q[i] . k[j] * scale)``. ``block`` (0:
    ``DEFAULT_BLOCK`` capped by S) must divide S."""
    s = q.shape[1]
    block = resolve_block(s, block)
    if s % block:
        raise ValueError(f"the attention block {block} does not divide the sequence length {s}")
    if q.shape[0] != k.shape[0] * group or k.shape[:2] != v.shape[:2]:
        raise ValueError(f"queries {q.shape} are not {group} rows for each of the keys' {k.shape} "
                         f"and values' {v.shape}")
    return attention(q, k, v, float(scale), block, int(group))
