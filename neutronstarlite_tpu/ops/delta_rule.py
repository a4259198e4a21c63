"""The gated delta rule of a linear-attention mixer, chunk by chunk.

Per row (a sequence and head) the mixer keeps a state ``S [dk, dv]`` and
walks the positions: with keys ``k_t`` and queries ``q_t`` in ``R^dk``,
values ``v_t`` in ``R^dv``, a log-decay ``g_t <= 0`` per key channel and a
write strength ``beta_t`` in (0, 1),

    S' = Diag(exp(g_t)) S_{t-1}
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T        o_t = S_t^T q_t

Position by position that is ``S`` sequential rank-one updates. Here the
positions are cut into chunks of ``C`` and the state is carried from chunk
to chunk by a ``lax.scan``; inside a chunk everything is matrix products
(the delta rule's WY form). With ``G_r = sum_{i <= r} g_i`` the log-decay
from the chunk's start through row ``r`` and ``u_i = beta_i (v_i -
S'_i^T k_i)`` the rank-one updates' right factors:

    A_ij = sum_c k_i[c] k_j[c] exp(G_i[c] - G_j[c])          (j < i)
    B_rj = sum_c q_r[c] k_j[c] exp(G_r[c] - G_j[c])          (j <= r)
    (I + Diag(beta) A) U = Diag(beta) (V - (K * exp(G)) S_0)
    O = (Q * exp(G)) S_0 + B U
    S_C = Diag(exp(G_C)) S_0 + (K * exp(G_C - G))^T U

The decay is per channel, so ``A`` and ``B`` do not factor into one product
of decayed keys: ``exp(G_i) / exp(G_j)`` would divide by a cumulative decay
that a strong gate underflows. Only differences ``G_r - G_j`` with ``r >=
j`` are ever exponentiated. A chunk is cut into sub-blocks of ``SUB_BLOCK``
rows: a pair in different sub-blocks is split at the first row ``f`` of
the later one, ``exp(G_i - G_f) exp(G_f - G_j)`` with both exponents ``<=
0``, which is one product of rows decayed down from ``f`` with earlier rows
raised up to ``f``; a pair inside one sub-block gets its own ``exp(G_i -
G_j)`` (a ``[sub, sub, dk]`` value per sub-block, the only place where the
work is not a matrix product).

**A decay per head** (a log-decay whose last axis is 1: one scalar a row
and position, the gated delta rule of a per-head gate) goes through the
same chunk scan, state carry, inverse and checkpoints by a second, cheaper
path to ``A`` and ``B``. The decay leaves the sum over channels,

    A_ij = (k_i . k_j) exp(G_i - G_j)          B_rj = (q_r . k_j) exp(G_r - G_j)

so the pairs are one product of the chunk's rows and a ``[C, C]`` mask of
``exp(G_i - G_j)`` taken for ``i >= j`` only (an exponent ``<= 0``; never a
quotient of two cumulative decays): no sub-blocks, no ``[sub, sub, dk]``
value. **More value heads than key heads**: ``v``, the decay, beta and the
state have ``r`` rows for each row of ``q`` and ``k`` (row ``n`` of the
keys serves rows ``r n .. r n + r - 1`` of the values, each with its own
decay, write strength and state). The keys' rows reach their value heads
inside the scan, a chunk at a time: ``k k^T`` and ``q k^T`` are made once
a key head and repeated under ``r`` decay masks, and the chunk's own ``[C,
dk]`` rows of q and k are repeated where a product needs them beside a
value head's state. That costs a copy of ``2 r C dk`` float32 a key head
and chunk (128 KB at ``C`` 64, ``dk`` 128, ``r`` 2), which lives as long
as its chunk; no ``[rows of v, S, dk]`` copy of q or k exists in HBM (at 2
x 8,192 positions and 32 value heads that copy would be 134 MB each for q
and k in bfloat16, written once forward and once more in the backward's
recomputation, and their gradients summed over the pair again).

The unit lower-triangular system is solved by its inverse, which for a
strictly lower ``L`` (``L^C = 0``) is the finite product ``(I - L)(I +
L^2)(I + L^4)...``: ``log2 C`` products in place of ``C`` sequential rows.

Numeric policy: the cumulative log-decay, the system and its inverse, and
the state are float32; the other products read their operands through
``cast`` (bfloat16 under PRECISION:bfloat16) and accumulate in float32.

Backward: autodiff through the scan, each chunk under ``jax.checkpoint``:
what is kept per chunk is the state it started from (``[rows, dk, dv]``
float32) and its slice of the inputs; the ``[C, C]`` systems and the
sub-blocks' exponentials are made again in the backward pass. No
``custom_vjp``: at the benchmark's size the kept states are 0.54 GB a
layer, live only while that layer's backward runs. Making what no state
enters (``A``, ``B``, the inverse, the solved system) for all chunks at
once before the scan, 512 (row, chunk) pairs a step of a ``lax.map``, was
tried and was slower on the chip (a step of the benchmark's cell 1.80 s
for 1.52 s, my chip runs, PR 33): the sub-blocks' ``[sub, sub, dk]``
exponentials then pass through HBM.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

DEFAULT_CHUNK = 64
SUB_BLOCK = 16


def _dot(spec: str, a, b):
    precision = lax.Precision.HIGHEST if a.dtype == jnp.float32 else None
    return jnp.einsum(spec, a, b, precision=precision, preferred_element_type=jnp.float32)


def chunk_log_decay(g: jax.Array, chunk: int) -> jax.Array:
    """``g [N, S, dk]`` (log-decay a position; ``[N, S, 1]``: one a head)
    -> ``[N, S / chunk, chunk, dk]`` float32: the log-decay from each
    chunk's start through each of its rows."""
    n, s, dk = g.shape
    return jnp.cumsum(g.astype(jnp.float32).reshape(n, s // chunk, chunk, dk), axis=2)


def unit_lower_inverse(lower: jax.Array) -> jax.Array:
    """``(I + lower)^-1`` for strictly lower-triangular ``lower [..., C,
    C]`` float32."""
    c = lower.shape[-1]
    inverse, power = jnp.eye(c, dtype=lower.dtype) - lower, lower
    for _ in range(max(0, math.ceil(math.log2(c)) - 1)):
        power = _dot("...ij,...jk->...ik", power, power)
        inverse = inverse + _dot("...ij,...jk->...ik", inverse, power)
    return inverse


def _pair_decays(q, k, gc, sub: int, cast):
    """(``A`` strictly lower, ``B`` lower, both ``[N, C, C]`` float32) of
    one chunk: the decayed key-key and query-key products above."""
    n, c, dk = k.shape
    nb = c // sub
    g4, k4, q4 = (t.reshape(n, nb, sub, dk) for t in (gc, k, q))
    first = g4[:, :, :1]  # the log-decay at each sub-block's first row
    down = jnp.exp(g4 - first)  # a row, decayed down from its sub-block's first row
    up = jnp.exp(jnp.minimum(first - gc[:, None], 0.0))  # [N, nb, C, dk]: an earlier row, up to it
    rows = jnp.concatenate([k4 * down, q4 * down], axis=2)
    across = _dot("nbrc,nbjc->nbrj", cast(rows), cast(k[:, None] * up))  # [N, nb, 2 sub, C]
    inside = jnp.exp(jnp.minimum(g4[:, :, :, None] - g4[:, :, None], 0.0))  # [N, nb, sub, sub, dk]
    r = lax.broadcasted_iota(jnp.int32, (c, c), 0)
    j = lax.broadcasted_iota(jnp.int32, (c, c), 1)
    same = jnp.eye(nb, dtype=jnp.float32)[:, None, :, None]  # sub-block a, row, sub-block b, row

    def whole(rows4, across_part, mask):
        within = jnp.sum(rows4[:, :, :, None] * k4[:, :, None] * inside, axis=-1)  # [N, nb, sub, sub]
        within = (within[:, :, :, None] * same).reshape(n, c, c)
        pairs = jnp.where(j // sub < r // sub, across_part.reshape(n, c, c), within)
        return jnp.where(mask, pairs, 0.0)

    return whole(k4, across[:, :, :sub], j < r), whole(q4, across[:, :, sub:], j <= r)


def _head_pair_decays(q, k, gc, cast):
    """``A`` and ``B`` as ``_pair_decays`` gives them, for a decay per head
    ``gc [N, C, 1]`` and ``q, k [N / r, C, dk]``: one product a key head,
    ``r`` masks of ``exp(G_i - G_j)``."""
    n, c, _ = gc.shape
    pairs = _dot("nrc,njc->nrj", cast(jnp.concatenate([k, q], axis=1)), cast(k))  # [N / r, 2 C, C]
    pairs = jnp.repeat(pairs, n // k.shape[0], axis=0) if n != k.shape[0] else pairs
    g = gc[..., 0]
    decay = jnp.exp(jnp.minimum(g[:, :, None] - g[:, None, :], 0.0))  # rows i >= j are read
    r = lax.broadcasted_iota(jnp.int32, (c, c), 0)
    j = lax.broadcasted_iota(jnp.int32, (c, c), 1)
    return (jnp.where(j < r, pairs[:, :c] * decay, 0.0),
            jnp.where(j <= r, pairs[:, c:] * decay, 0.0))


def _chunk(state, q, k, v, gc, beta, sub: int, cast):
    """One chunk of every row: (the state after it, its outputs ``[N, C,
    dv]`` float32) from the state before it ``[N, dk, dv]``; ``q`` and
    ``k`` may have fewer rows (key heads) than ``v`` (value heads)."""
    q, k, v = (t.astype(jnp.float32) for t in (q, k, v))
    per_head = gc.shape[-1] == 1
    if per_head:
        a, b = _head_pair_decays(q, k, gc, cast)
    if v.shape[0] != k.shape[0]:  # a key head's rows beside each of its value heads
        q, k = (jnp.repeat(t, v.shape[0] // k.shape[0], axis=0) for t in (q, k))
    if not per_head:
        a, b = _pair_decays(q, k, gc, sub, cast)
    inverse = unit_lower_inverse(beta[:, :, None] * a)
    through = jnp.exp(gc)  # the decay from the chunk's start through each row
    solved = _dot("nrs,nsd->nrd", inverse, jnp.concatenate([v, k * through], -1) * beta[:, :, None])
    dv = v.shape[-1]
    held = cast(state)
    u = solved[..., :dv] - _dot("nrc,ncd->nrd", cast(solved[..., dv:]), held)
    out = _dot("nrc,ncd->nrd", cast(q * through), held) + _dot("nrs,nsd->nrd", cast(b), cast(u))
    last = gc[:, -1:]
    state = jnp.exp(last)[:, 0, :, None] * state + _dot(
        "nrc,nrd->ncd", cast(k * jnp.exp(last - gc)), cast(u))
    return state, out


def chunked_delta_rule(q, k, v, log_decay, beta, cast=lambda t: t):
    """``o [N, S, dv]`` (``v``'s dtype) of the recurrence above from ``q, k
    [N / r, S, dk]``, ``v [N, S, dv]``, ``log_decay [N, S / C, C, dk]``
    (``chunk_log_decay``; a last axis of 1 is a decay per head) and ``beta
    [N, S]``; every row starts from a zero state."""
    n, chunks, c, _ = log_decay.shape
    dk = k.shape[-1]
    sub = math.gcd(c, SUB_BLOCK)
    if n % k.shape[0] or q.shape != k.shape or v.shape[0] != n:
        raise ValueError(f"queries {q.shape} and keys {k.shape} do not serve a whole number of "
                         f"the {n} rows of values {v.shape} and decays")

    def by_chunk(t):
        return jnp.moveaxis(t.reshape(t.shape[0], chunks, c, *t.shape[2:]), 1, 0)

    @jax.checkpoint
    def body(state, part):
        return _chunk(state, *part, sub, cast)

    parts = (by_chunk(q), by_chunk(k), by_chunk(v), jnp.moveaxis(log_decay, 1, 0),
             by_chunk(beta.astype(jnp.float32)))
    _, out = lax.scan(body, jnp.zeros((n, dk, v.shape[-1]), jnp.float32), parts)
    return jnp.moveaxis(out, 0, 1).reshape(n, chunks * c, -1).astype(v.dtype)


def gated_delta_rule(q, k, v, g, beta, chunk: int = DEFAULT_CHUNK, cast=lambda t: t):
    """The recurrence from the log-decay a position ``g [N, S, dk]`` (``[N,
    S, 1]``: a decay per head)."""
    return chunked_delta_rule(q, k, v, chunk_log_decay(g, chunk), beta, cast)
