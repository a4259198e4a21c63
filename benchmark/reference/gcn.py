"""Plain reference GCN: float32 ``jax.numpy``, no kernels, no tables, and
nothing of the program's: it starts from the benchmark's own edge list.

The layer is the reference system's (NeutronStar, toolkits/GCN_CPU.hpp:
215-228): aggregate the normalised neighbour rows, then
``relu(W * batchnorm(a))`` on hidden layers and ``W * a`` on the last.
Departures from Kipf & Welling's GCN, all the reference system's own: the
aggregation comes before the dense layer, hidden layers batch-normalise
over the vertex axis with the batch's own statistics (also in eval mode),
and there is no bias. Dropout is off: the comparison is made in eval mode.
An edge u -> v weighs ``1 / sqrt(out_degree(u) * in_degree(v))``
(core/ntsBaseOp.hpp:194-197), repeated edges counted as often as they
occur; ``degrees`` and ``edge_weights`` compute it from the edge list.

``aggregate`` walks an edge list sorted by the vertex it sums into, in
fixed-size chunks; each chunk is a gather, a multiply and a sorted segment
sum into the rows of its range. Sorted by destination it is the forward
pass; the same edges sorted by source, with the roles swapped, are its
transpose, which the backward pass needs. Matrix products run at
``jax.default_matmul_precision("highest")``: on a TPU a float32 product is
otherwise rounded to bfloat16 passes.

The loss is the masked mean negative log-likelihood of the softmax
(GCN_CPU.hpp:187-196). Gradients are plain backpropagation: ``jax.vjp``
through each dense layer, the transposed aggregation between them.

``block_*`` is the same layer over the bipartite blocks a neighbour
sampler draws (no batch norm: the sampled model has none), weighted by the
whole graph's degrees as the reference system's sampled GCN weighs them.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, List, NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

EDGE_CHUNK = 1 << 18
BN_EPS = 1e-5


class Edges(NamedTuple):
    """An edge list sorted by ``into``: row ``into[e]`` of the result sums
    ``weight[e]`` times row ``take[e]`` of the input."""

    take: np.ndarray
    into: np.ndarray
    weight: np.ndarray


def degrees(src: np.ndarray, dst: np.ndarray, v_num: int) -> Tuple[np.ndarray, np.ndarray]:
    """(out_degree, in_degree), a repeated edge counted as often as it occurs."""
    return np.bincount(src, minlength=v_num), np.bincount(dst, minlength=v_num)


def edge_weights(src: np.ndarray, dst: np.ndarray, out_degree: np.ndarray,
                 in_degree: np.ndarray) -> np.ndarray:
    """1 / sqrt(out_degree(src) * in_degree(dst)) per edge u -> v."""
    d = np.maximum(out_degree, 1)[src].astype(np.float64) * np.maximum(in_degree, 1)[dst]
    return (1.0 / np.sqrt(d)).astype(np.float32)


@partial(jax.jit, static_argnames=("span",), donate_argnums=(0,))
def _accumulate(out, x, take, into_local, weight, row0, span: int):
    """out[row0 + d] += sum of weight * x[take] over the chunk's edges with
    local target d (targets ascend within a chunk)."""
    vals = x[take] * weight[:, None]
    part = jax.ops.segment_sum(
        vals, into_local, num_segments=span, indices_are_sorted=True
    )
    cur = jax.lax.dynamic_slice_in_dim(out, row0, span, axis=0)
    return jax.lax.dynamic_update_slice_in_dim(out, cur + part, row0, axis=0)


def aggregate(edges: Edges, x: jax.Array, v_num: int, chunk: int = EDGE_CHUNK) -> jax.Array:
    """[V, f] -> [V, f]: out[v] = sum over the edges into v of w * x[take]."""
    take, into, weight = edges
    e_num = len(take)
    n_chunks = -(-e_num // chunk)
    starts = np.arange(n_chunks) * chunk
    ends = np.minimum(starts + chunk, e_num)
    first = into[starts].astype(np.int64)
    last = into[ends - 1].astype(np.int64)
    span = int((last - first).max()) + 1
    span = 1 << (span - 1).bit_length()  # few distinct shapes across graphs
    out = jnp.zeros((v_num + span, x.shape[1]), jnp.float32)  # room for the last window
    for c in range(n_chunks):
        lo, hi = int(starts[c]), int(ends[c])
        pad = chunk - (hi - lo)
        t = np.pad(take[lo:hi].astype(np.int32), (0, pad))
        d = np.pad((into[lo:hi].astype(np.int64) - first[c]).astype(np.int32),
                   (0, pad), constant_values=span - 1)
        w = np.pad(weight[lo:hi].astype(np.float32), (0, pad))  # padding adds 0
        out = _accumulate(out, x, t, d, w, int(first[c]), span)
    return out[:v_num]


def _held_in(x, dtype):
    """``x`` as ``dtype`` would hold it, in float32 again; ``dtype`` None:
    as it is. Only the control (benchmark/control.py) names a dtype: the
    reference in a precision below the configuration's, which the check
    has to tell from the reference itself. The tensor is scaled by a power
    of two so that its largest entry sits near the top of the dtype's
    range, as an 8-bit path scales what it stores: unscaled, a loss
    gradient of 1e-5 an entry is nought in fp8 and the control would fail
    for that alone. Rounded by ``reduce_precision`` to the dtype's exponent
    and mantissa bits: a cast there and back is one the TPU's compiler may
    leave out inside a jitted function (it keeps the excess precision), and
    on the chip did (my chip runs, PR 27). A gradient passes through
    unrounded (the backward pass holds in ``dtype`` what it aggregates, by
    a call of its own)."""
    if dtype is None:
        return x
    info = jnp.finfo(dtype)
    top = jnp.max(jnp.abs(x)) / float(2.0 ** (info.maxexp - 2))  # e4m3: entries up to 128
    scale = jnp.exp2(jnp.ceil(jnp.log2(jnp.where(top > 0, top, 1.0))))
    held = jax.lax.reduce_precision(x / scale, exponent_bits=info.nexp, mantissa_bits=info.nmant) * scale
    return x + jax.lax.stop_gradient(held - x)


@partial(jax.jit, static_argnames=("dtype",))
def _hidden(a, layer, dtype=None):
    bn = layer["bn"]
    mean = jnp.mean(a, axis=0, keepdims=True)
    var = jnp.var(a, axis=0, keepdims=True)
    h = (a - mean) * jax.lax.rsqrt(var + BN_EPS) * bn["gamma"] + bn["beta"]
    with jax.default_matmul_precision("highest"):
        return _held_in(jax.nn.relu(_held_in(h, dtype) @ _held_in(layer["W"], dtype)), dtype)


@partial(jax.jit, static_argnames=("dtype",))
def _last(a, layer, dtype=None):
    with jax.default_matmul_precision("highest"):
        return _held_in(_held_in(a, dtype) @ _held_in(layer["W"], dtype), dtype)


@jax.jit
def masked_nll(logits, label, mask01):
    """Mean over the masked rows of -log softmax(logits)[label]."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    picked = jnp.take_along_axis(logp, label[:, None], axis=-1)[:, 0]
    return -(picked * mask01).sum() / jnp.maximum(mask01.sum(), 1.0)


def _as_f32(tree):
    return jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), tree)


def aggregate_input(by_dst: Edges, feature: np.ndarray, dtype=None) -> jax.Array:
    """The first layer's aggregate of the features, which no weight
    enters: a caller that runs the model at two sets of weights makes it
    once and hands it to both."""
    x = _held_in(jnp.asarray(feature, jnp.float32), dtype)
    return aggregate(by_dst, x, feature.shape[0])


def full_forward(by_dst: Edges, params: List[Dict], feature: np.ndarray,
                 a0: jax.Array = None, dtype=None) -> np.ndarray:
    """Eval-mode logits [V, classes] of the whole graph; ``a0`` is
    ``aggregate_input`` of the same edges and features, where the caller
    has it. ``dtype`` (the control's alone): what every aggregation reads
    and every product takes and gives, the logits among them, is held in
    it, as the program's bfloat16 products give bfloat16."""
    v_num = feature.shape[0]
    x = a0 if a0 is not None else aggregate_input(by_dst, feature, dtype)
    for i, layer in enumerate(_as_f32(params)):
        dense = _last if i == len(params) - 1 else _hidden
        x = dense(x if i == 0 else aggregate(by_dst, x, v_num), layer, dtype)
    return np.asarray(x)


def full_loss_and_grads(by_dst: Edges, by_src: Edges, params: List[Dict],
                        feature: np.ndarray, label: np.ndarray, mask01: np.ndarray,
                        a0: jax.Array = None, dtype=None):
    """(logits, loss, gradients in the layout of ``params``) of the
    eval-mode forward. ``by_src`` holds the edges of ``by_dst`` with the
    roles swapped (``take`` the destination, ``into`` the source); ``a0``
    and ``dtype`` as in ``full_forward`` (the backward aggregation reads
    in ``dtype`` too)."""
    v_num = feature.shape[0]
    x = a0 if a0 is not None else aggregate_input(by_dst, feature, dtype)
    pulls = []
    for i, layer in enumerate(_as_f32(params)):
        dense = partial(_last if i == len(params) - 1 else _hidden, dtype=dtype)
        x, pull = jax.vjp(dense, x if i == 0 else aggregate(by_dst, x, v_num), layer)
        pulls.append(pull)
    loss, dx = jax.value_and_grad(masked_nll)(x, jnp.asarray(label), jnp.asarray(mask01))
    grads: List = [None] * len(pulls)
    for i in reversed(range(len(pulls))):
        da, grads[i] = pulls[i](dx)
        grads[i] = jax.tree.map(lambda g: _held_in(g, dtype), grads[i])
        if i:  # the features are not trained: nothing flows into them
            dx = aggregate(by_src, _held_in(da, dtype), v_num)
    return np.asarray(x), float(loss), jax.tree.map(np.asarray, grads)


Hop = Tuple[np.ndarray, np.ndarray, np.ndarray]  # (src_local, dst_local, weight)


def block_weights(nodes: Sequence[np.ndarray], hops: Sequence[Tuple[np.ndarray, np.ndarray, np.ndarray]],
                  out_degree: np.ndarray, in_degree: np.ndarray) -> List[Hop]:
    """The blocks ``(src_local, dst_local, valid)`` with the reference's own
    weights: an edge from ``nodes[i][src_local]`` to ``nodes[i + 1][dst_local]``
    weighs as it does in the whole graph, a padding slot 0."""
    out = []
    for i, (src_local, dst_local, valid) in enumerate(hops):
        w = edge_weights(nodes[i][src_local], nodes[i + 1][dst_local], out_degree, in_degree)
        out.append((np.asarray(src_local), np.asarray(dst_local), np.where(valid, w, np.float32(0))))
    return out


def _block_logits(params, x0, hops: Sequence[Hop], caps: Sequence[int]):
    x = x0
    for i, (layer, (src, dst, weight)) in enumerate(zip(params, hops)):
        vals = x[src] * weight[:, None]
        a = jax.ops.segment_sum(vals, dst, num_segments=int(caps[i + 1]))
        with jax.default_matmul_precision("highest"):
            x = a @ layer["W"]
        if i < len(params) - 1:
            x = jax.nn.relu(x)
    return x


def block_forward(params: List[Dict], x0: np.ndarray, hops: Sequence[Hop],
                  caps: Sequence[int]) -> np.ndarray:
    """Logits [caps[-1], classes] over sampled blocks. ``x0`` are the
    feature rows of the input vertices; hop i is (src_local, dst_local,
    weight) from level i to level i + 1, which has caps[i + 1] rows."""
    hops = [tuple(jnp.asarray(a) for a in hop) for hop in hops]
    return np.asarray(_block_logits(_as_f32(params), jnp.asarray(x0, jnp.float32), hops, caps))


def block_loss_and_grads(params: List[Dict], x0: np.ndarray, hops: Sequence[Hop],
                         caps: Sequence[int], label: np.ndarray, mask01: np.ndarray):
    """(loss, gradients in the layout of ``params``) over sampled blocks."""
    hops = [tuple(jnp.asarray(a) for a in hop) for hop in hops]
    x0 = jnp.asarray(x0, jnp.float32)

    def loss(p):
        return masked_nll(_block_logits(p, x0, hops, caps), jnp.asarray(label), jnp.asarray(mask01))

    value, grads = jax.value_and_grad(loss)(_as_f32(params))
    return float(value), jax.tree.map(np.asarray, grads)
