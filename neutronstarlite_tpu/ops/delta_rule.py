"""The gated delta rule of a linear-attention mixer, chunk by chunk.

Per row (a sequence and head) the mixer keeps a state ``S [dk, dv]`` and
walks the positions: with keys ``k_t`` and queries ``q_t`` in ``R^dk``,
values ``v_t`` in ``R^dv``, a log-decay ``g_t <= 0`` per key channel and a
write strength ``beta_t`` in (0, 1),

    S' = Diag(exp(g_t)) S_{t-1}
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T        o_t = S_t^T q_t

Position by position that is ``S`` sequential rank-one updates. Here the
positions are cut into chunks of ``C`` and the state is carried from chunk
to chunk (a kernel on a TPU, a ``lax.scan`` elsewhere: the last paragraph);
inside a chunk everything is matrix products
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
raised up to ``f``, read through ``cast``. Inside a sub-block the same
split is made once more in float32, at the first row of its later half
(``EXACT_ROWS`` 8: one tile of sublanes), and a pair inside one half gets
its own ``exp(G_i - G_j)`` (a ``[8, 8, dk]`` value per half, the only place
where the work is not a matrix product; its cotangents are written out,
``_exact``).

**A decay per head** (a log-decay whose last axis is 1: one scalar a row
and position, the gated delta rule of a per-head gate) goes through the
same chunk walk, state carry, inverse and kept states by a second, cheaper
path to ``A`` and ``B``. The decay leaves the sum over channels,

    A_ij = (k_i . k_j) exp(G_i - G_j)          B_rj = (q_r . k_j) exp(G_r - G_j)

so the pairs are one product of the chunk's rows and a ``[C, C]`` mask of
``exp(G_i - G_j)`` taken for ``i >= j`` only (an exponent ``<= 0``; never a
quotient of two cumulative decays): no sub-blocks, no ``[sub, sub, dk]``
value. **More value heads than key heads**: ``v``, the decay, beta and the
state have ``r`` rows for each row of ``q`` and ``k`` (row ``n`` of the
keys serves rows ``r n .. r n + r - 1`` of the values, each with its own
decay, write strength and state). The keys' rows reach their value heads
inside the walk, a chunk at a time: ``k k^T`` and ``q k^T`` are made once
a key head and repeated under ``r`` decay masks, and the chunk's own ``[C,
dk]`` rows of q and k are repeated where a product needs them beside a
value head's state. That costs a copy of ``2 r C dk`` float32 a key head
and chunk (128 KB at ``C`` 64, ``dk`` 128, ``r`` 2), which lives as long
as its chunk; no ``[rows of v, S, dk]`` copy of q or k exists in HBM (at 2
x 8,192 positions and 32 value heads that copy would be 134 MB each for q
and k in bfloat16, written once forward and once more in the backward's
recomputation, and their gradients summed over the pair again).

The unit lower-triangular system is solved by its inverse, exact in
float32 as the system is. The diagonal blocks of ``SUB_BLOCK`` rows are
inverted by elimination, elementwise; with ``D`` their inverses and ``M = D
(L - its diagonal blocks)``, strictly lower by blocks (``M^(C / SUB_BLOCK) =
0``), the rest is the finite product ``(I - M)(I + M^2)... D``: four ``[C,
C]`` products at ``C`` 64 where ``(I - L)(I + L^2)(I + L^4)...`` over single
rows took ten, and two more (``-X^T g X^T``) for its cotangent.

Numeric policy: the cumulative log-decay, the system and its inverse, and
the state are float32, float32 products at the highest precision; the other
products read their operands through ``cast`` (bfloat16 under
PRECISION:bfloat16) and accumulate in float32.

**Two forms of the chunk walk, one ``_chunk``.** ``recurrence`` is a
``jax.custom_vjp``: its forward leaves every chunk's starting state (``[N, S
/ C, dk, dv]`` float32: 0.54 GB a layer at the benchmark's size, kept only
while that layer's backward runs; a forward pass with no backward to follow
runs the same program and drops them, so that a layer made again under
``jax.checkpoint`` gives its backward the bits its forward gave),
its backward walks the chunks from the
last to the first with the state's cotangent carried, making each chunk
again from its state and differentiating it (``jax.vjp`` of ``_chunk``: one
statement of the mathematics). On a TPU, at head widths in multiples of 128
and a chunk in multiples of ``SUB_BLOCK``, both walks are Pallas kernels
(``kda_recurrence``, ``kda_recurrence_backward``): a grid over (block of
``ROWS_PER_BLOCK`` rows of v beside their key heads, ``CHUNKS_PER_STEP``
chunks), the second axis in order, the state (the backward's cotangent of
it) in a VMEM scratch that is zeroed at a row block's first step, every
intermediate of a chunk in VMEM; q, k, v, the log-decay and beta are read
where they lie (no transposed or widened copy, no ``[rows of v, S, dk]``
copy of q or k; a row's scalars, beta and a decay per head, as ``[N, S / C,
1, C]``: the same bytes, a block of them whole tiles), the cotangents of q
and k summed over a key head's value heads inside the kernel. Elsewhere the same two walks are a ``lax.scan`` and its reverse
(``lax.platform_dependent``: chosen when the program is lowered), which is
what tier-1 compares the kernels with (Pallas' interpret mode there).
``_chunk``, ``chunk_log_decay`` and ``chunked_delta_rule`` are looked up on
the module when a step is traced, whichever form runs. Making what no state
enters for all chunks at once before the scan, in plain JAX, was tried and
was slower on the chip (PR 33: the sub-blocks' exponentials then pass
through HBM).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_CHUNK = 64
SUB_BLOCK = 16
ROWS_PER_BLOCK = 8  # rows of v (value heads) a grid step of the kernels holds, their key heads beside them
CHUNKS_PER_STEP = 4  # chunks a grid step of the kernels walks
LANES = 128  # the kernels take head widths in multiples of it
VMEM_LIMIT = 96 * 2 ** 20  # bytes of VMEM a kernel may use (a v5e has 128 MiB)


def _dot(spec: str, a, b):
    precision = lax.Precision.HIGHEST if a.dtype == jnp.float32 else None
    return jnp.einsum(spec, a, b, precision=precision, preferred_element_type=jnp.float32)


def chunk_log_decay(g: jax.Array, chunk: int) -> jax.Array:
    """``g [N, S, dk]`` (log-decay a position; ``[N, S, 1]``: one a head)
    -> ``[N, S / chunk, chunk, dk]`` float32: the log-decay from each
    chunk's start through each of its rows."""
    n, s, dk = g.shape
    return jnp.cumsum(g.astype(jnp.float32).reshape(n, s // chunk, chunk, dk), axis=2)


def _eye(c: int, dtype):
    return (lax.broadcasted_iota(jnp.int32, (c, c), 0) == lax.broadcasted_iota(jnp.int32, (c, c), 1)).astype(dtype)


def _block_inverse(lower: jax.Array) -> jax.Array:
    """``unit_lower_inverse`` of one small block, by elimination: column
    ``j``'s entries leave the rows below it, ``C - 1`` rank-one updates of
    the identity, elementwise (no matrix product: a ``[16, 16]`` one uses a
    sixty-fourth of a matrix unit)."""
    c = lower.shape[-1]
    inverse = _eye(c, lower.dtype)
    for j in range(c - 1):
        inverse = inverse - lower[..., :, j: j + 1] * inverse[..., j: j + 1, :]
    return inverse


@jax.custom_vjp
def unit_lower_inverse(lower: jax.Array) -> jax.Array:
    """``(I + lower)^-1`` for strictly lower-triangular ``lower [..., C,
    C]`` float32, exact in float32 as the system is: the diagonal blocks of
    ``SUB_BLOCK`` rows by ``_block_inverse`` (``D``), then with ``M = D
    (lower - its diagonal blocks)``, strictly lower by blocks and so ``M^(C
    / SUB_BLOCK) = 0``, the finite product ``(I - M)(I + M^2)(I + M^4)...
    D``: four products at ``C`` 64. Its cotangent is ``-X^T g X^T`` of the
    inverse ``X``: two products, not the transposes of the forward's."""
    c = lower.shape[-1]
    size = math.gcd(c, SUB_BLOCK)
    if size == c:
        return _block_inverse(lower)
    lead = lower.shape[:-2]
    # the diagonal blocks side by side on a new leading axis: one elimination for all of them
    solved = _block_inverse(jnp.stack([lower[..., lo: lo + size, lo: lo + size] for lo in range(0, c, size)]))
    blocks = jnp.concatenate([jnp.concatenate([
        *([jnp.zeros((*lead, size, lo), lower.dtype)] if lo else []),
        lax.index_in_dim(solved, lo // size, axis=0, keepdims=False),
        *([jnp.zeros((*lead, size, c - lo - size), lower.dtype)] if lo + size < c else []),
    ], axis=-1) for lo in range(0, c, size)], axis=-2)
    earlier = (lax.broadcasted_iota(jnp.int32, (c, c), 1) // size
               < lax.broadcasted_iota(jnp.int32, (c, c), 0) // size)  # a column of an earlier block
    power = _dot("...ij,...jk->...ik", blocks, jnp.where(earlier, lower, 0.0))
    inverse = _eye(c, lower.dtype) - power
    for _ in range(max(0, math.ceil(math.log2(c // size)) - 1)):
        power = _dot("...ij,...jk->...ik", power, power)
        inverse = inverse + _dot("...ij,...jk->...ik", inverse, power)
    return _dot("...ij,...jk->...ik", inverse, blocks)


def _unit_lower_inverse_fwd(lower):
    inverse = unit_lower_inverse(lower)
    return inverse, inverse


def _unit_lower_inverse_bwd(inverse, g):
    return (-_dot("...ik,...lk->...il", _dot("...ji,...jk->...ik", inverse, g), inverse),)


unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


def _lower(pairs, strict: bool):
    """``pairs [N, R, C]``, rows ``C - R ..`` of a ``[C, C]`` triangle: the
    entries on and above (``strict``) or above the diagonal zeroed."""
    _, rows, c = pairs.shape
    r = lax.broadcasted_iota(jnp.int32, (1, rows, c), 1) + (c - rows)
    j = lax.broadcasted_iota(jnp.int32, (1, rows, c), 2)
    return jnp.where(j < r if strict else j <= r, pairs, 0.0)


EXACT_ROWS = 8  # rows of a sub-block whose pairs get their own exponentials: one tile of sublanes


def _pair_exponentials(k_own, g_own):
    """(``exp(G_i - G_j)``, that times ``k_j``), both ``[N, i, j, c]``."""
    inside = jnp.exp(jnp.minimum(g_own[:, :, None] - g_own[:, None], 0.0))
    return inside, k_own[:, None] * inside


@jax.custom_vjp
def _exact(k_own, q_own, g_own):
    """The decayed key-key and query-key products of a few rows with each
    other, both ``[N, R, R]`` (row ``i``, row ``j``; read for ``j <= i``)
    from ``k_own, q_own, g_own [N, R, dk]``: every pair its own ``exp(G_i -
    G_j)``, a value of four axes ``[N, i, j, c]`` and the only place where
    the work is not a matrix product. Its cotangents are written out: a
    third of the four-axis passes autodiff makes (the log-decay's cotangent
    is the rows' times the rows)."""
    _, decayed = _pair_exponentials(k_own, g_own)
    return (jnp.sum(k_own[:, :, None] * decayed, axis=-1), jnp.sum(q_own[:, :, None] * decayed, axis=-1))


def _exact_fwd(k_own, q_own, g_own):
    return _exact(k_own, q_own, g_own), (k_own, q_own, g_own)


def _exact_bwd(saved, g):
    k_own, q_own, g_own = saved
    g_k, g_q = (t[..., None] for t in g)
    inside, decayed = _pair_exponentials(k_own, g_own)
    by_k, by_q = jnp.sum(g_k * decayed, axis=2), jnp.sum(g_q * decayed, axis=2)  # row i's: [N, i, c]
    # row j's, short of its own k_j: the sum over i of (g_k k_i + g_q q_i) exp(G_i - G_j)
    by_j = jnp.sum((g_k * k_own[:, :, None] + g_q * q_own[:, :, None]) * inside, axis=1)  # [N, j, c]
    return by_k + by_j, by_q, k_own * (by_k - by_j) + q_own * by_q


_exact.defvjp(_exact_fwd, _exact_bwd)


def _within(k_own, q_own, g_own):
    """``_exact`` of a sub-block's rows, with the pairs of its later half
    with its earlier half as one float32 product split at the later half's
    first row ``f``, ``exp(G_i - G_f) exp(G_f - G_j)`` with both exponents
    ``<= 0`` (halved down to ``EXACT_ROWS``): a quarter of the four-axis
    work a sub-block of 16 would take whole."""
    rows = k_own.shape[1]
    if rows <= EXACT_ROWS or rows % 2:
        return tuple(_lower(t, strict) for t, strict in zip(_exact(k_own, q_own, g_own), (True, False)))
    half = rows // 2
    (k_early, q_early, g_early), (k_late, q_late, g_late) = (
        [t[:, rows] for t in (k_own, q_own, g_own)] for rows in (slice(0, half), slice(half, None)))
    first = g_late[:, :1]
    down = jnp.exp(g_late - first)
    up = jnp.exp(jnp.minimum(first - g_early, 0.0))
    corner = _dot("nrc,njc->nrj", jnp.concatenate([k_late * down, q_late * down], axis=1), k_early * up)
    zero = jnp.zeros((k_own.shape[0], half, half), jnp.float32)

    def whole(early, late, corner):  # [[early, 0], [corner, late]]
        return jnp.concatenate([jnp.concatenate([early, zero], axis=2),
                                jnp.concatenate([corner, late], axis=2)], axis=1)

    return tuple(map(whole, _within(k_early, q_early, g_early), _within(k_late, q_late, g_late),
                     (corner[:, :half], corner[:, half:])))


def _pair_decays(q, k, gc, sub: int, cast):
    """(``A`` strictly lower, ``B`` lower, both ``[N, C, C]`` float32) of
    one chunk: the decayed key-key and query-key products above, a
    sub-block's rows at a time."""
    n, c, dk = k.shape
    a, b = [], []
    for lo in range(0, c, sub):
        g_own, k_own, q_own = (t[:, lo: lo + sub] for t in (gc, k, q))
        parts = [[], []]
        if lo:
            first = g_own[:, :1]  # the log-decay at the sub-block's first row
            down = jnp.exp(g_own - first)  # a row, decayed down from its sub-block's first row
            up = jnp.exp(jnp.minimum(first - gc[:, :lo], 0.0))  # an earlier row, up to it
            across = _dot("nrc,njc->nrj", cast(jnp.concatenate([k_own * down, q_own * down], axis=1)),
                          cast(k[:, :lo] * up))  # [N, 2 sub, lo]
            parts = [[across[:, :sub]], [across[:, sub:]]]
        for part, within in zip(parts, _within(k_own, q_own, g_own)):
            part.append(within)
            if lo + sub < c:
                part.append(jnp.zeros((n, sub, c - lo - sub), jnp.float32))
        a.append(jnp.concatenate(parts[0], axis=2))
        b.append(jnp.concatenate(parts[1], axis=2))
    return jnp.concatenate(a, axis=1), jnp.concatenate(b, axis=1)


def _head_pair_decays(q, k, gc, cast):
    """``A`` and ``B`` as ``_pair_decays`` gives them, for a decay per head
    ``gc [N, C, 1]`` and ``q, k [N / r, C, dk]``: one product a key head,
    ``r`` masks of ``exp(G_i - G_j)``."""
    n, c, _ = gc.shape
    pairs = _dot("nrc,njc->nrj", cast(jnp.concatenate([k, q], axis=1)), cast(k))  # [N / r, 2 C, C]
    pairs = jnp.repeat(pairs, n // k.shape[0], axis=0) if n != k.shape[0] else pairs
    decay = jnp.exp(jnp.minimum(gc - jnp.swapaxes(gc, 1, 2), 0.0))  # rows i >= j are read
    return _lower(pairs[:, :c] * decay, True), _lower(pairs[:, c:] * decay, False)


def _chunk(state, q, k, v, gc, beta, sub: int, cast):
    """One chunk of every row: (the state after it, its outputs ``[N, C,
    dv]`` float32) from the state before it ``[N, dk, dv]``; ``q`` and
    ``k`` may have fewer rows (key heads) than ``v`` (value heads). Values
    of two and three axes (four for a sub-block's exponentials), so that
    the kernels' compiler takes it as the scan does."""
    q, k, v = (t.astype(jnp.float32) for t in (q, k, v))
    per_head = gc.shape[-1] == 1
    if per_head:
        a, b = _head_pair_decays(q, k, gc, cast)
    if v.shape[0] != k.shape[0]:  # a key head's rows beside each of its value heads
        q, k = (jnp.repeat(t, v.shape[0] // k.shape[0], axis=0) for t in (q, k))
    if not per_head:
        a, b = _pair_decays(q, k, gc, sub, cast)
    write = beta[:, :, None]
    inverse = unit_lower_inverse(write * a)
    through = jnp.exp(gc)  # the decay from the chunk's start through each row
    solved = _dot("nrs,nsd->nrd", inverse, jnp.concatenate([v, k * through], -1) * write)
    dv = v.shape[-1]
    held = cast(state)
    u = solved[..., :dv] - _dot("nrc,ncd->nrd", cast(solved[..., dv:]), held)
    out = _dot("nrc,ncd->nrd", cast(q * through), held) + _dot("nrs,nsd->nrd", cast(b), cast(u))
    last = gc[:, -1:]
    # what the chunk leaves of the state, a factor a key channel [N, dk, 1]; a row's one decay is
    # spread over the channels first: the kernels' compiler broadcasts an axis at a time
    kept = jnp.exp(jnp.swapaxes(jnp.broadcast_to(gc, k.shape)[:, -1:], 1, 2))
    state = kept * state + _dot(
        "nrc,nrd->ncd", cast(k * jnp.exp(last - gc)), cast(u))
    return state, out


def _by_chunk(t, chunk: int):
    """``[N, S, ...]`` -> ``[S / C, N, C, ...]``: what a scan walks."""
    return jnp.moveaxis(t.reshape(t.shape[0], t.shape[1] // chunk, chunk, *t.shape[2:]), 1, 0)


def _whole(t):
    """``_by_chunk``'s way back."""
    return jnp.moveaxis(t, 0, 1).reshape(t.shape[1], t.shape[0] * t.shape[2], *t.shape[3:])


def _scan_parts(q, k, v, decay, beta, chunk: int):
    return (*(_by_chunk(t, chunk) for t in (q, k, v)),
            _by_chunk(decay[..., None] if decay.ndim == 2 else decay, chunk), _by_chunk(beta, chunk))


# The four walks are jitted: a stack's delta-rule layers share one trace of each (a layer's operands
# have one shape), keyed by the chunk function too, which is looked up on the module at every call.
def _walk_jit(*static):
    return functools.partial(jax.jit, static_argnames=("step", "cast", "chunk", *static))


@_walk_jit()
def _scan_forward(q, k, v, decay, beta, *, step, cast, chunk: int):
    """The chunk walk as a ``lax.scan``: (``o [N, S, dv]`` in ``v``'s
    dtype, every chunk's starting state ``[N, S / C, dk, dv]`` float32)."""
    sub = math.gcd(chunk, SUB_BLOCK)

    def body(state, part):
        after, out = step(state, *part, sub, cast)
        return after, (out.astype(v.dtype), state)

    start = jnp.zeros((v.shape[0], k.shape[-1], v.shape[-1]), jnp.float32)
    _, (out, states) = lax.scan(body, start, _scan_parts(q, k, v, decay, beta, chunk))
    return _whole(out), jnp.moveaxis(states, 0, 1)


@_walk_jit()
def _scan_backward(q, k, v, decay, beta, states, g, *, step, cast, chunk: int):
    """The cotangents of the five operands from the output's ``g [N, S,
    dv]``: the chunks from the last to the first, each made again from the
    state it started from and differentiated."""
    sub = math.gcd(chunk, SUB_BLOCK)

    def body(d_state, part):
        *operands, state, g_out = part
        _, pull = jax.vjp(lambda *a: step(*a, sub, cast), state, *operands)
        d_state, *d_operands = pull((d_state, g_out.astype(jnp.float32)))
        return d_state, d_operands

    parts = (*_scan_parts(q, k, v, decay, beta, chunk), jnp.moveaxis(states, 1, 0), _by_chunk(g, chunk))
    _, (dq, dk, dv, dg, db) = lax.scan(body, jnp.zeros_like(states[:, 0]), parts, reverse=True)
    dg = _whole(dg)
    return _whole(dq), _whole(dk), _whole(dv), dg[..., 0] if decay.ndim == 2 else dg, _whole(db)


def _kernel_parts(q_ref, k_ref, v_ref, g_ref, b_ref, t, chunk: int, per_head: bool):
    """(the positions of chunk ``t`` in a grid step's blocks, the chunk as
    ``_chunk`` takes it): a row's scalars (beta; a decay per head) lie
    along the lanes of a ``[rows, chunks, 1, C]`` block."""
    at = pl.ds(pl.multiple_of(t * chunk, chunk), chunk)
    return at, (q_ref[:, at], k_ref[:, at], v_ref[:, at],
                g_ref[:, t][:, 0][:, :, None] if per_head else g_ref[:, at], b_ref[:, t][:, 0])


def _forward_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, states_ref, state_ref,
                    *, step, chunk, cast, per_head):
    sub = math.gcd(chunk, SUB_BLOCK)

    @pl.when(pl.program_id(1) == 0)
    def _():
        state_ref[...] = jnp.zeros_like(state_ref)

    def body(t, _):
        at, parts = _kernel_parts(q_ref, k_ref, v_ref, g_ref, b_ref, t, chunk, per_head)
        states_ref[:, t] = state = state_ref[...]
        state_ref[...], out = step(state, *parts, sub, cast)
        o_ref[:, at] = out.astype(o_ref.dtype)

    lax.fori_loop(0, q_ref.shape[1] // chunk, body, None)


def _backward_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, s_ref, go_ref,
                     dq_ref, dk_ref, dv_ref, dg_ref, db_ref, d_state_ref, *, step, chunk, cast, per_head):
    sub = math.gcd(chunk, SUB_BLOCK)
    chunks = q_ref.shape[1] // chunk

    @pl.when(pl.program_id(1) == 0)
    def _():
        d_state_ref[...] = jnp.zeros_like(d_state_ref)

    def body(i, _):
        t = chunks - 1 - i
        at, parts = _kernel_parts(q_ref, k_ref, v_ref, g_ref, b_ref, t, chunk, per_head)
        _, pull = jax.vjp(lambda *a: step(*a, sub, cast), s_ref[:, t], *parts)
        d_state_ref[...], dq, dk, dv, dg, db = pull((d_state_ref[...], go_ref[:, at].astype(jnp.float32)))
        dq_ref[:, at], dk_ref[:, at], dv_ref[:, at] = dq, dk, dv
        db_ref[:, t] = db[:, None, :]
        if per_head:
            dg_ref[:, t] = jnp.swapaxes(dg, 1, 2)
        else:
            dg_ref[:, at] = dg

    lax.fori_loop(0, chunks, body, None)


def rows_per_block(rows: int) -> int:
    """Rows of v a grid step of the kernels holds."""
    return min(ROWS_PER_BLOCK, rows)


def kernel_takes(key_rows: int, rows: int, positions: int, dk: int, dv: int, chunk: int) -> bool:
    """Whether the kernels take operands of these sizes: head widths in
    multiples of ``LANES``, a chunk in multiples of ``SUB_BLOCK``, whole
    row blocks, each with whole key heads."""
    block = rows_per_block(rows)
    return (dk % LANES == 0 and dv % LANES == 0 and chunk % SUB_BLOCK == 0 and positions % chunk == 0
            and rows % block == 0 and (block * key_rows) % rows == 0)


def _blocks(q, v, decay, chunk: int, back: bool):
    """(grid, block specs by operand, the state's scratch) of the kernels:
    a grid step takes ``ROWS_PER_BLOCK`` rows of v beside their key heads
    and ``CHUNKS_PER_STEP`` chunks, the steps of a row block in order (in
    the backward from the last to the first)."""
    n, s, dv = v.shape
    dk = q.shape[-1]
    rows = rows_per_block(n)
    keys = rows * q.shape[0] // n
    per = math.gcd(s // chunk, CHUNKS_PER_STEP)
    steps = s // (per * chunk)
    at = (lambda j: steps - 1 - j) if back else (lambda j: j)
    flat = pl.BlockSpec((rows, per, 1, chunk), lambda i, j: (i, at(j), 0, 0))  # a row's scalars, along the lanes
    spec = {
        "key": pl.BlockSpec((keys, per * chunk, dk), lambda i, j: (i, at(j), 0)),
        "value": pl.BlockSpec((rows, per * chunk, dv), lambda i, j: (i, at(j), 0)),
        "decay": flat if decay.ndim == 4 else pl.BlockSpec((rows, per * chunk, dk), lambda i, j: (i, at(j), 0)),
        "beta": flat,
        "states": pl.BlockSpec((rows, per, dk, dv), lambda i, j: (i, at(j), 0, 0)),
    }
    return (n // rows, steps), spec, pltpu.VMEM((rows, dk, dv), jnp.float32)


def _along_lanes(t, chunk: int):
    """A scalar a row and position ``[N, S]`` as the kernels' blocks take
    it, ``[N, S / C, 1, C]``: a block of any rows and chunks is then whole
    tiles."""
    return t.reshape(t.shape[0], t.shape[1] // chunk, 1, chunk) if t.ndim == 2 else t


def compiler_params(interpret: bool):
    """A kernel's compiler parameters on a chip (none in interpret mode): a
    grid whose first axis is parallel and whose second is walked in order,
    ``VMEM_LIMIT`` bytes of VMEM. ops/causal_attention.py's kernels take the
    same."""
    return {} if interpret else {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"), vmem_limit_bytes=VMEM_LIMIT)}


@_walk_jit("interpret")
def _kernel_forward(q, k, v, decay, beta, *, step, cast, chunk: int, interpret: bool):
    """``_scan_forward`` as one kernel: the state stays in VMEM from a row
    block's first chunk to its last."""
    n, s, dv = v.shape
    per_head = decay.ndim == 2
    decay, beta = _along_lanes(decay, chunk), _along_lanes(beta, chunk)
    grid, sp, scratch = _blocks(q, v, decay, chunk, back=False)
    return tuple(pl.pallas_call(
        functools.partial(_forward_kernel, step=step, chunk=chunk, cast=cast, per_head=per_head), grid=grid,
        in_specs=[sp["key"], sp["key"], sp["value"], sp["decay"], sp["beta"]],
        out_specs=[sp["value"], sp["states"]],
        out_shape=[jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct((n, s // chunk, q.shape[-1], dv), jnp.float32)],
        scratch_shapes=[scratch], interpret=interpret, name="kda_recurrence", **compiler_params(interpret),
    )(q, k, v, decay, beta))


@_walk_jit("interpret")
def _kernel_backward(q, k, v, decay, beta, states, g, *, step, cast, chunk: int, interpret: bool):
    """``_scan_backward`` as one kernel: the state's cotangent stays in
    VMEM from a row block's last chunk to its first."""
    shapes = decay.shape, beta.shape
    decay, beta = _along_lanes(decay, chunk), _along_lanes(beta, chunk)
    grid, sp, scratch = _blocks(q, v, decay, chunk, back=True)
    operands = (q, k, v, decay, beta)
    specs = [sp["key"], sp["key"], sp["value"], sp["decay"], sp["beta"]]
    *d_rows, d_decay, d_beta = pl.pallas_call(
        functools.partial(_backward_kernel, step=step, chunk=chunk, cast=cast, per_head=len(shapes[0]) == 2),
        grid=grid, in_specs=specs + [sp["states"], sp["value"]], out_specs=specs,
        out_shape=[jax.ShapeDtypeStruct(t.shape, t.dtype) for t in operands],
        scratch_shapes=[scratch], interpret=interpret, name="kda_recurrence_backward",
        **compiler_params(interpret),
    )(*operands, states, g)
    return (*d_rows, d_decay.reshape(shapes[0]), d_beta.reshape(shapes[1]))


def _walk(scan, kernel, form, q, k, v, *rest, chunk: int, **static):
    """One of the two forms of a walk: ``form`` "scan", "kernel" (Mosaic),
    "interpret" (the kernel in Pallas' interpret mode), or None: the kernel
    on a TPU where it takes the operands, else the scan (chosen when the
    program is lowered)."""
    scan = functools.partial(scan, step=_chunk, chunk=chunk, **static)
    if form == "scan" or (form is None and not kernel_takes(
            q.shape[0], *v.shape[:2], q.shape[-1], v.shape[-1], chunk)):
        return scan(q, k, v, *rest)
    kernel = functools.partial(kernel, step=_chunk, chunk=chunk, interpret=form == "interpret", **static)
    if form is not None:
        return kernel(q, k, v, *rest)
    return lax.platform_dependent(q, k, v, *rest, tpu=kernel, default=scan)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def recurrence(q, k, v, decay, beta, cast, chunk: int, form=None):
    """``chunked_delta_rule`` over the log-decay as the walks read it,
    ``[N, S, dk]`` or (a decay per head) ``[N, S]``, and a float32 beta."""
    return _recurrence_fwd(q, k, v, decay, beta, cast, chunk, form)[0]


def _recurrence_fwd(q, k, v, decay, beta, cast, chunk, form):
    # one forward program, with or without a backward to follow: a layer that is made again under
    # ``jax.checkpoint`` gives its backward pass the bits its forward pass gave (a router after it
    # chooses the same experts both times); the states a forward pass alone leaves are dropped
    out, states = _walk(_scan_forward, _kernel_forward, form, q, k, v, decay, beta, chunk=chunk, cast=cast)
    return out, (q, k, v, decay, beta, states)


def _recurrence_bwd(cast, chunk, form, saved, g):
    return _walk(_scan_backward, _kernel_backward, form, *saved, g, chunk=chunk, cast=cast)


recurrence.defvjp(_recurrence_fwd, _recurrence_bwd)


def chunked_delta_rule(q, k, v, log_decay, beta, cast=lambda t: t):
    """``o [N, S, dv]`` (``v``'s dtype) of the recurrence above from ``q, k
    [N / r, S, dk]``, ``v [N, S, dv]``, ``log_decay [N, S / C, C, dk]``
    (``chunk_log_decay``; a last axis of 1 is a decay per head) and ``beta
    [N, S]``; every row starts from a zero state."""
    n, chunks, c, wide = log_decay.shape
    if n % k.shape[0] or q.shape != k.shape or v.shape[0] != n:
        raise ValueError(f"queries {q.shape} and keys {k.shape} do not serve a whole number of "
                         f"the {n} rows of values {v.shape} and decays")
    decay = log_decay.reshape(n, chunks * c, *([] if wide == 1 else [wide]))
    return recurrence(q, k, v, decay, beta.astype(jnp.float32), cast, c)


def gated_delta_rule(q, k, v, g, beta, chunk: int = DEFAULT_CHUNK, cast=lambda t: t):
    """The recurrence from the log-decay a position ``g [N, S, dk]`` (``[N,
    S, 1]``: a decay per head)."""
    return chunked_delta_rule(q, k, v, chunk_log_decay(g, chunk), beta, cast)
