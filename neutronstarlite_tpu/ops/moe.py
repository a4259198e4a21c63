"""The routed-expert layer as a graph operator: a bipartite token -> expert
graph drawn anew every step.

Every token draws ``k`` of the layer's experts (a per-vertex neighbour draw:
the ``k`` largest router scores, what sample/fused.py does for vertex
graphs with random keys), the (token, expert) pairs are the edges, dispatch
is a gather of token rows by the edge list sorted by expert, the experts are
a grouped product over the ragged groups of that list, and combine is a
weighted segment sum of the edges back into their tokens (ops/segment.py's
operator: a scatter-add by the list's token column).

The layer is told which experts it holds (``first .. first + held``). It
routes over ALL the layer's experts, keeps the pairs whose expert is held,
and computes those experts' part of the result. There is no capacity
factor and no pair is ever dropped: the sorted list has room for every
pair a batch can send here (``tokens * k``: a token's ``k`` distinct
choices may all be held), the held pairs first, grouped by expert. The
list is only ever a list of places: its rows are made a chunk of
``GROUPED_CHUNK_ROWS`` places at a time, and every pass (dispatch, experts,
combine, forward and backward) is one walk that stops at the routed rows,
``sum(group_sizes)``, a number the device knows before the first pass. A
chunk that begins at or past it is not gathered, sliced, multiplied or
added; a batch whose every choice is held walks the whole list. What absent
experts would add is left out; nothing stands in for the chips that hold
them.

The walk is hand-paired (custom_vjp): a loop whose trip count is read on
the device has no transpose of its own. Forward and backward keep the
token rows, the weights and the plan, and nothing ``[tokens * k, hidden]``
exists in either.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from neutronstarlite_tpu.ops.segment import zero_cotangent


def route(scores: jax.Array, bias: jax.Array, per_token: int, scale: float):
    """(choice [T, k] int32, weight [T, k] float32) from the router's
    scores [T, experts] (float32): the ``k`` largest of ``scores + bias``
    (the bias corrects the choice only), weighted by their own scores,
    normalised over the chosen and scaled."""
    _, choice = lax.top_k(scores + bias, per_token)
    picked = jnp.take_along_axis(scores, choice, axis=-1)
    weight = picked / (picked.sum(axis=-1, keepdims=True) + 1e-20) * scale
    return choice.astype(jnp.int32), weight


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class Dispatch:
    """The step's edge list sorted by held expert.

    ``pair_of`` [T*k]: the pair ``t * k + slot`` at each place of the
    sorted list (held pairs first, by expert; the pairs of absent experts
    after them). ``slot_of`` [T, k]: where pair (t, k) sits in that list.
    ``held`` [T, k]: whether its expert is held here. ``group_sizes``
    [held experts]: rows per expert."""

    pair_of: jax.Array
    slot_of: jax.Array
    held: jax.Array
    group_sizes: jax.Array


def plan_dispatch(choice: jax.Array, first: int, n_held: int) -> Dispatch:
    tokens, per_token = choice.shape
    local = choice - first
    held = (local >= 0) & (local < n_held)
    key = jnp.where(held, local, n_held).reshape(-1)  # absent experts sort last
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    slot_of = jnp.zeros_like(order).at[order].set(
        jnp.arange(order.shape[0], dtype=jnp.int32), unique_indices=True
    ).reshape(tokens, per_token)
    group_sizes = jnp.sum(
        key[:, None] == jnp.arange(n_held, dtype=key.dtype)[None, :], axis=0, dtype=jnp.int32
    )
    return Dispatch(pair_of=order, slot_of=slot_of, held=held, group_sizes=group_sizes)


GMM_TILING = (512, 512, 512)  # rows, contracted, columns a tile of the Pallas kernel


def _gmm_tpu(x, w, group_sizes):
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    rows_tile = math.gcd(x.shape[0], GMM_TILING[0])  # the kernel wants whole row tiles
    return gmm(x, w, group_sizes, preferred_element_type=x.dtype,
               tiling=(rows_tile,) + GMM_TILING[1:])


def _gmm_plain(x, w, group_sizes):
    precision = lax.Precision.HIGHEST if x.dtype == jnp.float32 else None
    return lax.ragged_dot(x, w, group_sizes, precision=precision,
                          preferred_element_type=x.dtype)


def grouped_matmul(x: jax.Array, w: jax.Array, group_sizes: jax.Array) -> jax.Array:
    """[R, a] x [G, a, b] -> [R, b] in ``x``'s dtype: rows
    ``sum(group_sizes[:g]) ..`` times ``w[g]``, accumulated in float32. Its
    work follows the group sizes, and the rows past the last group are not
    written at all (zeros on the CPU, whatever the buffer held on the TPU):
    whoever reads the result masks them.

    On the TPU the Pallas grouped product of jax.experimental (megablox
    ``gmm``, hand-paired backward by ``tgmm``); elsewhere ``lax.ragged_dot``,
    the same sums, chosen when the program is lowered
    (``lax.platform_dependent``), so a compile for a described TPU takes the
    TPU's branch. Both lower for the v5e; at the cell's shapes (24,576 rows
    over 8 experts, 2048 x 1408) the SwiGLU's three products forward and
    backward take 11.3 ms by ``gmm`` and 18.5 ms by ``ragged_dot`` (the
    TPU's own Mosaic lowering of it), a further 8,192 rows 2.9 ms against
    5.2 ms, the gradients 1.3e-4 apart by the norm (my chip run, PR 28):
    ``gmm`` is kept."""
    return lax.platform_dependent(x, w, group_sizes, tpu=_gmm_tpu, default=_gmm_plain)


# Rows a step of the walk takes. A layer's routed path, forward and backward, at the three
# cells' shapes, milliseconds at 8,192 / 16,384 / 32,768 rows a chunk (my chip runs, PR 36):
# 57.8 / 47.7 / 45.8 (24,577 routed rows of 196,608), 17.8 / 23.3 / 28.5 (4,223 of 131,072),
# 26.5 / 25.2 / 29.7 (9,992 of 163,840); 107.1, 56.7 and 64.9 with every pass over the whole
# list. A small chunk walks fewer rows past the routed ones; every chunk pays two scatter-adds
# into [tokens, hidden] float32, which the TPU's compiler makes of a sort of the chunk's
# tokens, a gather of its rows into that order and a sorted scatter: 1.1 ms for 4,096 rows,
# 2.7 for 8,192, 3.2 for 16,384, 4.3 for 32,768 (a gather: 37 to 58 ns a row at any size).
GROUPED_CHUNK_ROWS = 16384
# the step's named scopes (models/seqlm.py ``SCOPES``) of the walk's three parts
DISPATCH, EXPERTS, COMBINE = "seq/moe/dispatch", "seq/moe/experts", "seq/moe/combine"


def chunk_of(list_rows: int, chunk_rows: int = GROUPED_CHUNK_ROWS) -> int:
    """Rows a step of the walk takes of a list of ``list_rows`` places: the
    largest divisor of the list that ``chunk_rows`` holds."""
    return math.gcd(list_rows, chunk_rows)


def rows_walked(n_routed: int, list_rows: int, chunk_rows: int = GROUPED_CHUNK_ROWS) -> int:
    """Places of the sorted list a pass touches when ``n_routed`` of them
    are held: the chunks that begin below ``n_routed``, whole."""
    chunk = chunk_of(list_rows, chunk_rows)
    return -(-n_routed // chunk) * chunk


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class ExpertRows:
    """What the sorted list's rows are made from, before any is made: the
    token rows ``x`` [T, hidden] they are gathered from and the held
    experts' SwiGLU matrices they go through ([held, hidden, width] x2,
    [held, width, hidden]), all in the compute dtype."""

    x: jax.Array
    wg: jax.Array
    wu: jax.Array
    wd: jax.Array


def grouped_swiglu(xs, wg, wu, wd, group_sizes):
    """SwiGLU of each row by its own expert's weights; the rows past the
    last group are left as ``grouped_matmul`` leaves them."""
    g = grouped_matmul(xs, wg, group_sizes)
    u = grouped_matmul(xs, wu, group_sizes)
    return grouped_matmul(jax.nn.silu(g) * u, wd, group_sizes)


def _take(x, token):
    return x.at[token].get(mode="promise_in_bounds")


def _add_into(out, token, rows):
    return out.at[token].add(rows, mode="promise_in_bounds")


class _Walk:
    """The routed chunks of one plan's sorted list: what a pass reads of
    the plan, and the chunk ``i`` of it. Nothing here is as long as the
    list: a chunk's pairs, tokens and weights are taken inside its step."""

    def __init__(self, plan: Dispatch, weight, chunk_rows: int):
        self.chunk = chunk_of(plan.pair_of.shape[0], chunk_rows)
        self.pair_of, self.per_token = plan.pair_of, plan.slot_of.shape[1]
        self.weight = weight.reshape(-1)
        self.ends = jnp.cumsum(plan.group_sizes)
        self.starts = self.ends - plan.group_sizes
        self.n_routed = self.ends[-1]
        self.trips = (self.n_routed + self.chunk - 1) // self.chunk

    def part(self, i):
        """(pairs [chunk], tokens [chunk], weights [chunk, 1], rows per
        expert inside the chunk [held], which of its places are routed
        [chunk, 1]: the places past them hold pairs of absent experts)."""
        offset = i * self.chunk
        pair = lax.dynamic_slice(self.pair_of, (offset,), (self.chunk,))
        sizes = (jnp.clip(self.ends - offset, 0, self.chunk)
                 - jnp.clip(self.starts - offset, 0, self.chunk))
        routed = offset + jnp.arange(self.chunk, dtype=offset.dtype) < self.n_routed
        return pair, pair // self.per_token, _take(self.weight, pair)[:, None], sizes, routed[:, None]


def _combine(rows: ExpertRows, weight, plan: Dispatch, chunk_rows: int):
    walk = _Walk(plan, weight, chunk_rows)

    def step(i, out):
        _, token, w, sizes, routed = walk.part(i)
        with jax.named_scope(DISPATCH):
            xs = _take(rows.x, token)
        with jax.named_scope(EXPERTS):
            y = grouped_swiglu(xs, rows.wg, rows.wu, rows.wd, sizes)
        with jax.named_scope(COMBINE):
            # the chunk's last rows may lie past the routed ones, where the
            # grouped product wrote nothing: masked, never multiplied
            return _add_into(out, token, jnp.where(routed, y.astype(jnp.float32) * w, 0.0))

    out = jnp.zeros((plan.slot_of.shape[0], rows.wd.shape[-1]), jnp.float32)
    return lax.fori_loop(0, walk.trips, step, out)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def combine_rows(rows: ExpertRows, weight, plan: Dispatch, chunk_rows: int = GROUPED_CHUNK_ROWS):
    """[T, hidden] float32: ``sum_k weight[t, k] * SwiGLU_e(x[t])`` over the
    HELD pairs (t, k), ``e`` the pair's expert; ``weight`` [T, k].

    One walk of the sorted list's routed chunks, a loop whose trip count
    the device reads from the plan: each chunk's token rows are gathered
    (``DISPATCH``), go through their experts (``EXPERTS``: three grouped
    products) and are added, weighted, into their tokens (``COMBINE``: a
    scatter-add in float32). The backward is the same walk once more: the
    chunk is gathered and multiplied again, so nothing of a chunk outlives
    its step, and the dispatch's transpose is the other scatter-add."""
    return _combine(rows, weight, plan, chunk_rows)


def _combine_fwd(rows, weight, plan, chunk_rows):
    return _combine(rows, weight, plan, chunk_rows), (rows, weight, plan)


def _combine_bwd(chunk_rows, res, g):
    rows, weight, plan = res
    walk = _Walk(plan, weight, chunk_rows)
    f32 = functools.partial(jnp.zeros_like, dtype=jnp.float32)

    def step(i, carry):
        d_x, d_weight, d_wg, d_wu, d_wd = carry
        pair, token, w, sizes, routed = walk.part(i)
        with jax.named_scope(DISPATCH):
            xs = _take(rows.x, token)
        with jax.named_scope(EXPERTS):
            y, back = jax.vjp(lambda *a: grouped_swiglu(*a, sizes), xs, rows.wg, rows.wu, rows.wd)
        with jax.named_scope(COMBINE):
            g_rows = _take(g, token)
            d_w = jnp.sum(jnp.where(routed, g_rows * y.astype(jnp.float32), 0.0), axis=-1)
            d_weight = d_weight.at[pair].set(d_w, mode="promise_in_bounds", unique_indices=True)
            d_y = jnp.where(routed, g_rows * w, 0.0).astype(y.dtype)
        with jax.named_scope(EXPERTS):
            d_xs, *d_experts = back(d_y)
            # an expert's rows may lie in several chunks: their sum is kept in float32
            d_wg, d_wu, d_wd = (acc + d.astype(jnp.float32)
                                for acc, d in zip((d_wg, d_wu, d_wd), d_experts))
        with jax.named_scope(DISPATCH):
            # the transpose of the gather: each token sums its routed rows' cotangents
            d_x = _add_into(d_x, token, jnp.where(routed, d_xs.astype(jnp.float32), 0.0))
        return d_x, d_weight, d_wg, d_wu, d_wd

    d_x, d_weight, *d_experts = lax.fori_loop(0, walk.trips, step, (
        f32(rows.x), f32(walk.weight), f32(rows.wg), f32(rows.wu), f32(rows.wd)))
    d_rows = jax.tree.map(lambda d, of: d.astype(of.dtype), ExpertRows(d_x, *d_experts), rows)
    return d_rows, d_weight.reshape(weight.shape), jax.tree.map(zero_cotangent, plan)


combine_rows.defvjp(_combine_fwd, _combine_bwd)
