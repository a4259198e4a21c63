"""The routed-expert layer as a graph operator: a bipartite token -> expert
graph drawn anew every step.

Every token draws ``k`` of the layer's experts (a per-vertex neighbour draw:
the ``k`` largest router scores, what sample/fused.py does for vertex
graphs with random keys), the (token, expert) pairs are the edges, dispatch
is a gather of token rows by the edge list sorted by expert, the experts are
a grouped product over the ragged groups of that list, and combine is a
weighted segment sum of the edges back into their tokens (ops/segment.py's
operator; here every token has exactly ``k`` slots, so the segment sum is a
gather by the inverse permutation and a sum over ``k``: no scatter).

The layer is told which experts it holds (``first .. first + held``). It
routes over ALL the layer's experts, keeps the pairs whose expert is held,
and computes those experts' part of the result. There is no capacity
factor and no pair is ever dropped: the sorted list has room for every
pair a batch can send here (``tokens * k``: a token's ``k`` distinct
choices may all be held), the held pairs first, grouped by expert, and the
grouped product's work follows ``group_sizes``, the rows really routed.
What absent experts would add is left out; nothing stands in for the chips
that hold them.

Both gathers are hand-paired (custom_vjp): the transpose of the dispatch
gather is the combine's gather-and-sum and the other way round, so the
backward pass has no scatter-add either.
"""

from __future__ import annotations

import dataclasses
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

    @property
    def token_of(self) -> jax.Array:
        return self.pair_of // self.slot_of.shape[1]


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


def _slot_sum(y, plan: Dispatch, weight=None):
    """[T, f] float32: ``sum_k weight[t, k] * y[slot of (t, k)]`` over the
    HELD pairs (weight None: 1), one slot at a time so that no [T, k, f]
    value exists. The rows of pairs that are not held lie past the grouped
    product's last group, which leaves them unwritten (on the TPU whatever
    the buffer held, a NaN among it): they are masked, never multiplied."""
    out = jnp.zeros((plan.slot_of.shape[0], y.shape[1]), jnp.float32)
    for k in range(plan.slot_of.shape[1]):
        rows = y[plan.slot_of[:, k]].astype(jnp.float32)
        if weight is not None:
            rows = rows * weight[:, k, None]
        out = out + jnp.where(plan.held[:, k, None], rows, 0.0)
    return out


@jax.custom_vjp
def dispatch_rows(x, plan: Dispatch):
    """[T, f] -> [T*k, f]: the token row of every sorted pair."""
    return x[plan.token_of]


def _dispatch_fwd(x, plan):
    return x[plan.token_of], plan


def _dispatch_bwd(plan, g):
    # the transpose of the gather by the sorted list: each token sums the
    # cotangents of its held slots (a pair that is not held took its row
    # too, but nothing reads that copy: its cotangent is zero)
    return _slot_sum(g, plan).astype(g.dtype), jax.tree.map(zero_cotangent, plan)


dispatch_rows.defvjp(_dispatch_fwd, _dispatch_bwd)


def _combine(y, weight, plan: Dispatch):
    return _slot_sum(y, plan, weight)


@jax.custom_vjp
def combine_rows(y, weight, plan: Dispatch):
    """[T*k, f], [T, k] -> [T, f] float32: ``sum_k weight[t, k] * y[slot of
    (t, k)]`` over the held pairs (the rows of the others are zeros: the
    grouped product leaves rows past its last group so)."""
    return _combine(y, weight, plan)


def _combine_fwd(y, weight, plan):
    return _combine(y, weight, plan), (y, weight, plan)


def _combine_bwd(res, g):
    y, weight, plan = res
    d_weight = jnp.stack([
        jnp.sum(jnp.where(plan.held[:, k, None],
                          g * y[plan.slot_of[:, k]].astype(jnp.float32), 0.0), axis=-1)
        for k in range(plan.slot_of.shape[1])
    ], axis=1)
    # each sorted pair takes its token's cotangent times its own weight
    w_of_pair = jnp.where(plan.held, weight, 0.0).reshape(-1)[plan.pair_of]
    d_y = g[plan.token_of] * w_of_pair[:, None]
    return d_y.astype(y.dtype), d_weight, jax.tree.map(zero_cotangent, plan)


combine_rows.defvjp(_combine_fwd, _combine_bwd)


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


GROUPED_CHUNK_ROWS = 32768


def grouped_swiglu(xs, wg, wu, wd, group_sizes, cast, chunk_rows: int = GROUPED_CHUNK_ROWS):
    """SwiGLU of each sorted row by its own expert's weights
    ([held, hidden, width] x2, [held, width, hidden]). The list has room
    for every pair a batch can send here and is mostly empty (the held
    pairs come first), so it is walked in chunks of ``chunk_rows`` rows,
    each recomputed in the backward: the [rows, width] activations exist
    for one chunk at a time. A chunk past the routed rows has empty groups
    and costs the kernel its walk over an empty grid. (Keeping the first
    chunk's activations saves a fifth of the work a routed row costs and a
    gigabyte too many; skipping empty chunks by ``lax.cond`` cost 0.8 GB
    and 0.6% of the step: my chip runs, PR 28.)"""
    xs, wg, wu, wd = cast(xs), cast(wg), cast(wu), cast(wd)
    rows = xs.shape[0]
    chunk = math.gcd(rows, chunk_rows)
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes

    @jax.checkpoint
    def one(xs_c, offset):
        sizes = jnp.clip(ends - offset, 0, chunk) - jnp.clip(starts - offset, 0, chunk)
        g = grouped_matmul(xs_c, wg, sizes)
        u = grouped_matmul(xs_c, wu, sizes)
        return grouped_matmul(jax.nn.silu(g) * u, wd, sizes)

    offsets = jnp.arange(rows // chunk, dtype=group_sizes.dtype) * chunk
    _, out = lax.scan(lambda _, part: (None, one(*part)), None,
                      (xs.reshape(rows // chunk, chunk, -1), offsets))
    return out.reshape(rows, -1)
