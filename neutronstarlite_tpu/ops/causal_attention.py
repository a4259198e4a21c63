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
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from neutronstarlite_tpu.ops.fused_edge import NEG_INF, fused_finalize, online_softmax_fold

DEFAULT_BLOCK = 512


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


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _attention(q, k, v, scale, block, group):
    return _forward(q, k, v, scale, block, group)[0]


def _attention_fwd(q, k, v, scale, block, group):
    out, lse = _forward(q, k, v, scale, block, group)
    return out, (q, k, v, out, lse)


def _tile_grads(q_i, k_j, v_j, g_i, lse_i, delta_i, i, j, scale: float, block: int):
    """(p, ds) of one tile, recomputed from the saved log-normaliser."""
    z, real = _tile_scores(q_i, k_j, i, j, scale, block)
    p = jnp.where(real, jnp.exp(z - lse_i), 0.0)
    dp = _dot("nqd,nkd->nqk", g_i, v_j)
    return p.astype(v_j.dtype), (p * (dp - delta_i) * scale).astype(q_i.dtype)


def _attention_bwd(scale, block, group, res, g):
    q, k, v, out, lse = res
    n, s, dk = k.shape
    nb = s // block
    g = g.astype(v.dtype)
    delta = jnp.sum(out.astype(jnp.float32) * g.astype(jnp.float32), axis=-1, keepdims=True)

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


_attention.defvjp(_attention_fwd, _attention_bwd)


def causal_edge_attention(q: jax.Array, k: jax.Array, v: jax.Array, scale: float,
                          block: int = 0, group: int = 1) -> jax.Array:
    """``q`` [N * group, S, dk], ``k`` [N, S, dk], ``v`` [N, S, dv] -> [N *
    group, S, dv]: for each of the N independent (sequence, key/value head)
    pairs and each of the ``group`` query heads that share it (rows ``n *
    group ..`` of ``q``), destination ``i`` aggregates ``v[j]`` over ``j <=
    i`` weighted by ``softmax_j(q[i] . k[j] * scale)``. ``block`` (0:
    ``DEFAULT_BLOCK`` capped by S) must divide S."""
    s = q.shape[1]
    block = int(block) or min(DEFAULT_BLOCK, s)
    if s % block:
        raise ValueError(f"the attention block {block} does not divide the sequence length {s}")
    if q.shape[0] != k.shape[0] * group or k.shape[:2] != v.shape[:2]:
        raise ValueError(f"queries {q.shape} are not {group} rows for each of the keys' {k.shape} "
                         f"and values' {v.shape}")
    return _attention(q, k, v, float(scale), block, int(group))
