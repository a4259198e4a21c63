"""Plain reference of the hybrid stack Qwen3-Next-80B-A3B configures: float32
``jax.numpy``, every matrix product at
``jax.default_matmul_precision("highest")``, no kernel, no chunked form, no
grouped layout, nothing of the program's and nothing of the other
references'. ``x`` is the residual stream ``[T, hidden]`` of ONE sequence of
``T`` positions; a batch is a loop over its sequences.

**Norm** everywhere but the delta rule's output: ``n(x; w) = x *
rsqrt(mean(x^2) + eps) * (1 + w)`` (zero-centred: ``w`` starts at zero). No
bias anywhere but ``dt_bias``. Layer ``i`` (0-based) attends if ``(i + 1) %
full_attention_interval == 0``, else runs the delta rule; every layer's MLP
is the expert MLP: ``x += mixer(n(x; norm1)); x += mlp(n(x; norm2))``. Which
mixer a layer has is read from its weights (a layer with ``a_log`` runs the
delta rule, one with ``q_norm`` attends).

**Gated delta rule** (``Hk`` key heads, ``Hv`` value heads, heads of ``d``),
``h = n(x)``:

1. ``q~ = h Wq``, ``k~ = h Wk`` (``Hk d`` each), ``v~ = h Wv`` (``Hv d``);
   each channel through its own causal convolution over positions, ``y_t =
   sum_{i=0..K-1} w[:, i] z_{t-(K-1)+i}`` (zeros before the sequence's
   start), written out as the sum of its ``K`` shifted terms, then SiLU; per
   head ``q = q / |q| * d^-0.5``, ``k = k / |k|`` (``|.|`` as ``sqrt(sum
   squares + 1e-6)``). Key head ``j`` serves value heads ``r j .. r j + r -
   1``, ``r = Hv / Hk``: its q and k are repeated for each.
2. Per value head ``beta = sigmoid(h Wb)`` and ``g = -exp(A_log) *
   softplus(h Wa + dt_bias)``: one scalar a head and position.
3. Per value head a state ``S [d, d]``, zero at the sequence's start,
   **position by position** (a ``lax.scan`` over positions; cut into
   segments under ``jax.checkpoint`` so that a gradient keeps a state per
   segment and not per position, which is bookkeeping and no other formula):
   ``S' = exp(g_t) S``; ``S = S' + beta_t k_t (v_t - S'^T k_t)^T``; ``o_t =
   S^T q_t``.
4. ``z = h Wz`` (``Hv d``); per head ``y = o * rsqrt(mean(o^2) + eps) * w_o
   * silu(z)`` (``w_o`` plain: it starts at one); ``x += concat(heads) Wo``.

**Gated grouped-query attention** (``H`` query heads and ``Hkv`` key/value
heads of ``D`` dims): ``h Wq`` is ``[T, H, 2 D]``, per head the query then
its gate; ``k = h Wk``, ``v = h Wv`` ``[T, Hkv, D]``; ``q = n(q; w_q)``, ``k
= n(k; w_k)`` over a head's ``D`` dims (one weight for all heads); rotary on
the leading ``R`` dims of q and k (dim ``i`` pairs with ``i + R/2``,
frequency ``theta^(-2i / R)``), the rest as they are; K and V **repeated**
for each of the ``H / Hkv`` query heads that share them (query head ``m``
reads key/value head ``m // (H / Hkv)``); score ``q . k / sqrt(D)``;
position ``i`` sees every ``j <= i``; softmax; aggregate ``v``; ``x +=
(concat(heads) * sigmoid(gate)) Wo``.

**Expert MLP**, ``h = n(x)``: ``p = softmax(h Wr)`` over all ``num_experts``
in float32; the ``num_experts_per_tok`` largest of ``p + b`` (``b`` a
buffer, given: zeros); their ``p`` divided by their sum; ``x += sum_k w_k
E_k(h) + sigmoid(h w_sg) * S(h)``, every expert and the shared one a SwiGLU
``(silu(h Wg) * h Wu) Wd``. Then the final norm, the head, the mean
next-token cross-entropy (a sequence's last position has no target).

One chip's share (``Share``): the experts ``first .. first + held`` of every
layer and a slice of the vocabulary; the router keeps its width, the weights
are normalised over all chosen experts, the routed sum runs over the held
ones; what absent experts would add is left out, and that partial result
goes on to the next layer. ``held = num_experts`` is the uncut layer.

Departures from the published description, all stated in the
configuration's ``assumed``: the source convolves the concatenation of q, k
and v (the same thing channel by channel) and holds q, k, v, z and b, a as
two fused projections (a column order); no multi-token-prediction module;
no auxiliary loss; no state or attention reset at document boundaries; the
L2 norm's epsilon (1e-6) is assumed.

``choice`` (optional, ``[layers, T, k]``): the reference then *follows* that
choice of experts, the weights still from its own scores. ``dtype`` (the
control's alone): every matrix product, the recurrence's and the
attention's included, then reads its operands as that dtype would hold
them.

The weights come in the layout the program keeps them in: ``embed``, the
layers in runs of one mixer kind stacked on a leading axis under ``moe``,
``moe1``, ``moe2``, ... in the stack's order (there is no dense layer),
``norm``, ``head``. A layer's leaves:

    delta rule: norm1, wq, wk [hidden, Hk d], wv [hidden, Hv d], cq, ck [Hk
                d, K], cv [Hv d, K], wf [hidden, Hv] (the decay's ``Wa``),
                a_log, dt_bias [Hv], wb [hidden, Hv], wz [hidden, Hv d],
                o_norm [d], wo [Hv d, hidden], norm2
    attention:  norm1, wq [hidden, H 2 D], wk, wv [hidden, Hkv D], q_norm,
                k_norm [D], wo [H D, hidden], norm2
    experts:    router, eg, eu, ed, sg, su, sd, sgate [hidden, 1]
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

SEGMENT = 64  # positions between two kept states of the recurrence's gradient
NORM_EPS = 1e-6  # assumed: the L2 norm's


class Shape(NamedTuple):
    """The published sizes the equations need (``config.json``'s keys)."""

    hidden: int
    heads: int
    kv_heads: int
    head_dim: int
    rotary_dims: int
    theta: float
    routed: int
    per_token: int
    eps: float
    key_heads: int
    value_heads: int
    delta_dim: int

    @staticmethod
    def of(model: dict) -> "Shape":
        head = int(model["head_dim"])
        return Shape(
            hidden=int(model["hidden_size"]), heads=int(model["num_attention_heads"]),
            kv_heads=int(model["num_key_value_heads"]), head_dim=head,
            rotary_dims=int(round(head * float(model["partial_rotary_factor"]))),
            theta=float(model["rope_theta"]), routed=int(model["num_experts"]),
            per_token=int(model["num_experts_per_tok"]), eps=float(model["rms_norm_eps"]),
            key_heads=int(model["linear_num_key_heads"]),
            value_heads=int(model["linear_num_value_heads"]),
            delta_dim=int(model["linear_value_head_dim"]),
        )


class Share(NamedTuple):
    """Which routed experts are held here: ``first .. first + held``."""

    first: int
    held: int


# ---- the pieces

def _held_in(x, dtype):
    """``x`` as ``dtype`` would hold it, in float32 again (None: as it is):
    scaled by a power of two so that the largest entry sits near the top of
    the dtype's range, rounded to its exponent and mantissa bits, scaled
    back; a gradient passes through unrounded. The control alone names a
    dtype."""
    if dtype is None:
        return x
    info = jnp.finfo(dtype)
    top = jnp.max(jnp.abs(x)) / float(2.0 ** (info.maxexp - 2))
    scale = jnp.exp2(jnp.ceil(jnp.log2(jnp.where(top > 0, top, 1.0))))
    held = lax.reduce_precision(x / scale, exponent_bits=info.nexp, mantissa_bits=info.nmant) * scale
    return x + lax.stop_gradient(held - x)


def _mm(a, b, dtype):
    with jax.default_matmul_precision("highest"):
        return jnp.matmul(_held_in(a, dtype), _held_in(b, dtype))


def norm(x, weight, eps):
    """The zero-centred RMS norm."""
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * (1.0 + weight)


def unit(x):
    return x * lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + NORM_EPS)


def swiglu(h, wg, wu, wd, dtype=None):
    return _mm(jax.nn.silu(_mm(h, wg, dtype)) * _mm(h, wu, dtype), wd, dtype)


def short_conv(z, w):
    """``z [T, C]`` through the causal convolution ``w [C, K]``: the sum of
    its ``K`` shifted terms, the term ``i`` reading ``K - 1 - i`` positions
    back."""
    t, taps = z.shape[0], w.shape[1]
    out = jnp.zeros_like(z)
    for i in range(taps):
        back = taps - 1 - i
        shifted = jnp.concatenate([jnp.zeros((back, z.shape[1]), z.dtype), z[: t - back]])
        out = out + shifted * w[:, i]
    return out


def turn(x, pos, theta: float, dims: int):
    """``x [T, H, D]`` with its leading ``dims`` dimensions turned by the
    positions ``pos [T]``: dimension ``i < dims / 2`` pairs with ``i + dims
    / 2`` at the frequency ``theta^(-2 i / dims)``."""
    half = dims // 2
    freq = theta ** (-np.arange(half, dtype=np.float32) / half)
    angle = pos.astype(jnp.float32)[:, None, None] * freq
    a, b = x[..., :half], x[..., half:dims]
    return jnp.concatenate([a * jnp.cos(angle) - b * jnp.sin(angle),
                            b * jnp.cos(angle) + a * jnp.sin(angle), x[..., dims:]], axis=-1)


def delta_rule_tokens(q, k, v, g, beta, segment: int = SEGMENT):
    """``o [T, H, dv]`` of the recurrence, position by position, from ``q, k
    [T, H, dk]``, ``v [T, H, dv]`` and one log-decay and write strength a
    head and position, ``g, beta [T, H]``."""
    t, h, dk = k.shape

    def position(state, at):
        q_t, k_t, v_t, g_t, b_t = at
        with jax.default_matmul_precision("highest"):
            decayed = jnp.exp(g_t)[:, None, None] * state
            u = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", decayed, k_t))
            state = decayed + k_t[:, :, None] * u[:, None, :]
            return state, jnp.einsum("hkv,hk->hv", state, q_t)

    @jax.checkpoint
    def run(state, part):
        return lax.scan(position, state, part)

    seg = math.gcd(t, segment)
    parts = jax.tree.map(lambda a: a.reshape(t // seg, seg, *a.shape[1:]), (q, k, v, g, beta))
    _, out = lax.scan(run, jnp.zeros((h, dk, v.shape[-1]), jnp.float32), parts)
    return out.reshape(t, h, -1)


@partial(jax.checkpoint, static_argnums=(3, 4, 5))
def _operand(h, w, taps, d, scale, dtype):
    """One of q, k, v ``[T, heads, d]`` from the normed stream: product,
    convolution, SiLU and, with a ``scale``, unit length per head times it.
    Under ``jax.checkpoint`` (as ``_gates`` and ``_gated_output``): a
    gradient keeps the path's inputs and walks it again, which bounds the
    memory of a whole sequence's backward and changes no formula."""
    t = jax.nn.silu(short_conv(_mm(h, w, dtype), taps)).reshape(h.shape[0], -1, d)
    return t if scale is None else unit(t) * scale


@partial(jax.checkpoint, static_argnums=(5,))
def _gates(h, wa, a_log, dt_bias, wb, dtype):
    """(log-decay ``g``, write strength ``beta``), both ``[T, Hv]``."""
    g = -jnp.exp(a_log) * jax.nn.softplus(_mm(h, wa, dtype) + dt_bias)
    return g, jax.nn.sigmoid(_mm(h, wb, dtype))


@partial(jax.checkpoint, static_argnums=(5, 6))
def _gated_output(h, o, wz, o_norm, wo, eps, dtype):
    t, heads, d = o.shape
    z = _mm(h, wz, dtype).reshape(t, heads, d)
    y = o * lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True) + eps) * o_norm
    return _mm((y * jax.nn.silu(z)).reshape(t, heads * d), wo, dtype)


def delta_mixer(lp, x, shape: Shape, dtype=None):
    """``x`` (a whole sequence) plus its gated delta rule."""
    d, each = shape.delta_dim, shape.value_heads // shape.key_heads
    h = norm(x, lp["norm1"], shape.eps)
    q = _operand(h, lp["wq"], lp["cq"], d, d ** -0.5, dtype)
    k = _operand(h, lp["wk"], lp["ck"], d, 1.0, dtype)
    v = _operand(h, lp["wv"], lp["cv"], d, None, dtype)
    g, beta = _gates(h, lp["wf"], lp["a_log"], lp["dt_bias"], lp["wb"], dtype)
    q, k = jnp.repeat(q, each, axis=1), jnp.repeat(k, each, axis=1)  # key head j -> value heads r j ..
    o = delta_rule_tokens(_held_in(q, dtype), _held_in(k, dtype), _held_in(v, dtype), g, beta)
    return x + _gated_output(h, o, lp["wz"], lp["o_norm"], lp["wo"], shape.eps, dtype)


def keys_values(lp, x, shape: Shape, dtype=None):
    """(k, v), each ``[T, Hkv, D]``, of a whole sequence: the keys normed
    and turned."""
    t = x.shape[0]
    h = norm(x, lp["norm1"], shape.eps)
    k = norm(_mm(h, lp["wk"], dtype).reshape(t, shape.kv_heads, shape.head_dim), lp["k_norm"], shape.eps)
    k = turn(k, jnp.arange(t, dtype=jnp.int32), shape.theta, shape.rotary_dims)
    return k, _mm(h, lp["wv"], dtype).reshape(t, shape.kv_heads, shape.head_dim)


def attend(lp, x_q, pos_q, k, v, pos_k, shape: Shape, dtype=None):
    """``x_q`` (a block of queries of the sequence) plus its gated attention
    over the sequence's keys and values, repeated for each query head."""
    heads, dim = shape.heads, shape.head_dim
    h = norm(x_q, lp["norm1"], shape.eps)
    qg = _mm(h, lp["wq"], dtype).reshape(x_q.shape[0], heads, 2 * dim)
    q = turn(norm(qg[..., :dim], lp["q_norm"], shape.eps), pos_q, shape.theta, shape.rotary_dims)
    k, v = (jnp.repeat(a, heads // shape.kv_heads, axis=1) for a in (k, v))  # [T, H, D]
    with jax.default_matmul_precision("highest"):
        score = jnp.einsum("qhd,khd->hqk", _held_in(q, dtype), _held_in(k, dtype)) / np.sqrt(dim)
        score = jnp.where(pos_k[None, None, :] <= pos_q[None, :, None], score, -jnp.inf)
        p = jax.nn.softmax(score, axis=-1)
        out = jnp.einsum("hqk,khd->qhd", _held_in(p, dtype), _held_in(v, dtype))
    gated = out * jax.nn.sigmoid(qg[..., dim:])
    return x_q + _mm(gated.reshape(x_q.shape[0], heads * dim), lp["wo"], dtype)


def mixer(lp, x, shape: Shape, dtype=None, block: Optional[int] = None):
    """``x`` plus the layer's token mixer, whichever its weights name; the
    attention in blocks of ``block`` queries (None: all at once)."""
    if "a_log" in lp:
        return delta_mixer(lp, x, shape, dtype)
    pos = jnp.arange(x.shape[0], dtype=jnp.int32)
    k, v = keys_values(lp, x, shape, dtype)
    return jnp.concatenate([
        attend(lp, x[lo:hi], pos[lo:hi], k, v, pos, shape, dtype)
        for lo, hi in _blocks(x.shape[0], block or x.shape[0])
    ])


def router_scores(lp, h):
    """``softmax(h Wr)`` over all the experts, in float32 whatever ``dtype``
    the rest is held in."""
    return jax.nn.softmax(_mm(h, lp["router"], None), axis=-1)


def choose(scores, bias, per_token: int):
    return lax.top_k(scores + bias, per_token)[1]


def expert_parts(lp, x, bias, shape: Shape, share: Share, choice=None, dtype=None):
    """(routed part of the held experts, the gated shared part, the
    reference's own choice) of the expert layer at ``x``; ``choice`` given,
    the routed part follows it."""
    h = norm(x, lp["norm2"], shape.eps)
    scores = router_scores(lp, h)
    own = choose(scores, bias, shape.per_token)
    if choice is None:
        choice = own
    picked = jnp.take_along_axis(scores, choice, axis=-1)
    weight = picked / picked.sum(axis=-1, keepdims=True)

    def one_more(routed, expert):  # the held experts one after the other, every row through each
        e, wg, wu, wd = expert
        gate = jnp.sum(jnp.where(choice == share.first + e, weight, 0.0), axis=-1)
        return routed + gate[:, None] * swiglu(h, wg, wu, wd, dtype), None

    routed, _ = lax.scan(one_more, jnp.zeros_like(x),
                         (jnp.arange(share.held),) + tuple(lp[k][: share.held] for k in ROUTED))
    shared = jax.nn.sigmoid(_mm(h, lp["sgate"], dtype)) * swiglu(h, lp["sg"], lp["su"], lp["sd"], dtype)
    return routed, shared, own


def expert_mlp(lp, x, bias, shape: Shape, share: Share, choice=None, dtype=None):
    routed, shared, own = expert_parts(lp, x, bias, shape, share, choice, dtype)
    return x + routed + shared, own


def head_logits(params, x, shape: Shape, dtype=None):
    return _mm(norm(x, params["norm"], shape.eps), params["head"], dtype)


def nll_sum(logits, targets, weight):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, targets[:, None], axis=-1)[:, 0] * weight)


# ---- the stack

def n_expert_layers(params) -> int:
    runs = [v for k, v in params.items() if k.startswith("moe")]
    return sum(int(run["router"].shape[0]) for run in runs)


def expert_layers(params, upto: Optional[int] = None, start: int = 0) -> List[Dict[str, Any]]:
    """The layers ``start .. upto`` (None: to the last), unstacked, in the
    stack's order: the runs ``moe``, ``moe1``, ``moe2``, ... one after the
    other. Only the layers asked for are taken out of their run, each as a
    copy (of a host array too, where an index alone would be a view that
    keeps the whole run alive: at the cell's size a run is 1.7 GB)."""
    upto = n_expert_layers(params) if upto is None else upto
    out, i, at = [], 0, 0
    while f"moe{i or ''}" in params:
        run = params[f"moe{i or ''}"]
        for n in range(run["router"].shape[0]):
            if start <= at < upto:
                out.append(jax.tree.map(
                    lambda a: np.array(a[n]) if isinstance(a, np.ndarray) else a[n], run))
            at += 1
        i += 1
    return out


def _blocks(total: int, block: int):
    return [(lo, min(lo + block, total)) for lo in range(0, total, block)]


@partial(jax.jit, static_argnames=("shape", "share", "dtype", "block"))
def _layer(lp, x, bias, choice, shape, share, dtype, block):
    return expert_mlp(lp, mixer(lp, x, shape, dtype, block), bias, shape, share, choice, dtype)


def hidden_states(params, tokens, shape: Shape, share: Share, bias=None, choice=None,
                  block: int = 1024, dtype=None, upto: Optional[int] = None):
    """(the residual stream ``[T, hidden]`` after the last layer, the
    reference's own choice of experts ``[L, T, k]`` at the stream it
    computed, which follows ``choice`` where one is given) of one sequence
    ``tokens [T]``. ``upto``: stop before layer ``upto`` (the stream that
    layer reads)."""
    layers = expert_layers(params, upto)
    if bias is None:
        bias = jnp.zeros((len(layers), shape.routed), jnp.float32)
    x = jnp.asarray(params["embed"], jnp.float32)[jnp.asarray(tokens)]
    choices = []
    for i, lp in enumerate(layers):
        x, own = _layer(lp, x, bias[i], None if choice is None else choice[i],
                        shape, share, dtype, block)
        choices.append(own)
    return x, (jnp.stack(choices) if choices else None)


def targets_of(tokens) -> Tuple[np.ndarray, np.ndarray]:
    """(the next token of every position, 1 where a position has one) of a
    batch ``[sequences, T]``."""
    tokens = np.asarray(tokens)
    weight = np.ones(tokens.shape, np.float32)
    weight[:, -1] = 0.0
    return np.roll(tokens, -1, axis=1).astype(np.int32), weight


@partial(jax.jit, static_argnames=("shape", "dtype"))
def _head_nll(params, x, targets, weight, shape, dtype):
    return nll_sum(head_logits(params, x, shape, dtype), targets, weight)


def loss(params, batch, shape: Shape, share: Share, bias=None, choice=None,
         block: int = 1024, dtype=None):
    """(mean next-token cross-entropy of ``batch [sequences, T]``, the
    choice of experts ``[sequences, L, T, k]``)."""
    targets, weight = targets_of(batch)
    total, choices = 0.0, []
    for s in range(batch.shape[0]):
        x, ch = hidden_states(params, batch[s], shape, share, bias,
                              None if choice is None else choice[s], block, dtype)
        choices.append(ch)
        for lo, hi in _blocks(x.shape[0], block):
            total = total + _head_nll(params, x[lo:hi], targets[s, lo:hi], weight[s, lo:hi],
                                      shape, dtype)
    return total / float(weight.sum()), jnp.stack(choices)


# ---- gradients

def whole_loss(params, batch, shape: Shape, share: Share, bias=None, choice=None, dtype=None):
    """The loss as one differentiable expression (nothing jitted inside):
    what ``jax.grad`` walks at a small size."""
    targets, weight = targets_of(batch)
    layers = expert_layers(params)
    if bias is None:
        bias = jnp.zeros((len(layers), shape.routed), jnp.float32)
    total = 0.0
    for s in range(batch.shape[0]):
        x = params["embed"][jnp.asarray(batch[s])]
        for i, lp in enumerate(layers):
            x, _ = expert_mlp(lp, mixer(lp, x, shape, dtype), bias[i], shape, share,
                              None if choice is None else choice[s][i], dtype)
        total = total + nll_sum(head_logits(params, x, shape, dtype), targets[s], weight[s])
    return total / float(weight.sum())


def loss_and_grads(params, batch, shape: Shape, share: Share, bias=None, choice=None, dtype=None):
    """(loss, gradients in the layout of ``params``): every weight, at a
    size where one expression fits."""
    params = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
    return jax.value_and_grad(whole_loss)(params, batch, shape, share, bias, choice, dtype)


TAIL_LAYERS = 2  # the last layers whose gradients tail_loss_and_grads gives
ROUTED = ("eg", "eu", "ed")  # a layer's routed experts' matrices, [held, ., .] each
TAIL_EXPERTS = 8  # of the tail's last layer the first so many held experts' matrices are in the tail
PER_HEAD = ("a_log", "dt_bias")  # a delta-rule layer's two leaves of one entry a value head
BACK_BLOCK = 256  # queries a block of the attention's backward, at most


def tail_of(params) -> Dict[str, Any]:
    """The weights ``tail_loss_and_grads`` differentiates: the last
    ``TAIL_LAYERS`` layers (at the benchmark's cut the third delta-rule
    layer and the attention layer after it: one of each mixer), the final
    norm and the head. Of the routed experts' matrices (100.7M of a layer's
    138.6M entries at the published widths, 32 experts held) the last
    layer's first ``TAIL_EXPERTS`` experts' are in the tail and the others
    are not: a check holds several copies of the tail on the host, some in
    float64, and with every expert of both layers they do not fit beside
    the program in a one-chip machine's 40 GiB (``_pruned``). ``a_log`` and
    ``dt_bias`` (32 entries each) are not in the tail either: during the
    warm-up an Adam step is the gradient's sign times about one float32
    grain of such a weight, 12 of ``dt_bias``'s 32 entries move at all, and
    ONE entry whose tiny gradient changes sign under bfloat16 products reads
    0.55 by the norm (``a_log``: 0.35-0.40), where the fp8 control reads
    0.78 (my chip runs, PR 35, seed 3500000021). The decay's path is held
    through ``wf``, 65,536 entries behind the same ``exp(a_log)`` and
    softplus."""
    n = n_expert_layers(params)
    return {"layers": _pruned(expert_layers(params, start=max(n - TAIL_LAYERS, 0))),
            "norm": params["norm"], "head": params["head"]}


def _pruned(layers: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """The tail's layers without the routed experts' matrices, but for the
    last layer's first ``TAIL_EXPERTS`` experts (copies, not views), and
    without the leaves of one entry a head."""
    def first(a):
        return np.array(a[:TAIL_EXPERTS]) if isinstance(a, np.ndarray) else a[:TAIL_EXPERTS]

    out = [{k: v for k, v in lp.items() if k not in ROUTED + PER_HEAD} for lp in layers[:-1]]
    return out + [{k: first(v) if k in ROUTED else v for k, v in layers[-1].items()
                   if k not in PER_HEAD}]


@partial(jax.jit, static_argnames=("shape", "dtype"))
def _mixer(lp, x, shape, dtype):
    return mixer(lp, x, shape, dtype)


@partial(jax.jit, static_argnames=("shape", "dtype"))
def _mixer_back(lp, x, d_out, shape, dtype):
    _, back = jax.vjp(lambda lp, x: mixer(lp, x, shape, dtype), lp, x)
    return back(d_out)


@partial(jax.jit, static_argnames=("shape", "share", "dtype"))
def _experts_back(lp, x, bias, choice, d_out, shape, share, dtype):
    _, back = jax.vjp(
        lambda lp, x: expert_mlp(lp, x, bias, shape, share, choice, dtype)[0], lp, x)
    return back(d_out)


def _delta_layer_back(lp, x, bias, choice, d_out, shape, share, dtype, block):
    """(gradient of a delta-rule layer's weights, of its input) from the
    gradient of its output: the expert MLP block of positions by block,
    then the mixer the whole sequence at once (position by position its
    memory is linear in the positions)."""
    mid = _mixer(lp, x, shape, dtype)
    grads, d_mid = jax.tree.map(jnp.zeros_like, lp), []
    for lo, hi in _blocks(x.shape[0], block):
        g_lp, g_x = _experts_back(lp, mid[lo:hi], bias, None if choice is None else choice[lo:hi],
                                  d_out[lo:hi], shape, share, dtype)
        grads = jax.tree.map(jnp.add, grads, g_lp)
        d_mid.append(g_x)
    g_lp, d_x = _mixer_back(lp, x, jnp.concatenate(d_mid), shape, dtype)
    return jax.tree.map(jnp.add, grads, g_lp), d_x


@partial(jax.jit, static_argnames=("shape", "share", "dtype"))
def _attending_block_back(lp, kv, x_q, pos_q, pos_k, bias, choice, d_out, shape, share, dtype):
    _, back = jax.vjp(
        lambda lp, kv, x_q: expert_mlp(lp, attend(lp, x_q, pos_q, *kv, pos_k, shape, dtype), bias,
                                       shape, share, choice, dtype)[0],
        lp, kv, x_q)
    return back(d_out)


@partial(jax.jit, static_argnames=("shape", "dtype"))
def _kv(lp, x, shape, dtype):
    return keys_values(lp, x, shape, dtype)


@partial(jax.jit, static_argnames=("shape", "dtype"))
def _kv_back(lp, x, d_kv, shape, dtype):
    _, back = jax.vjp(lambda lp, x: keys_values(lp, x, shape, dtype), lp, x)
    return back(d_kv)


def _attending_layer_back(lp, x, bias, choice, d_out, shape, share, dtype, block):
    """(gradient of an attention layer's weights, of its input) from the
    gradient of its output, block of queries by block of queries (the
    ``[H, T, T]`` float32 probabilities of a whole sequence are 4.3 GB at
    8,192 positions): the keys and values of the sequence are made once,
    their gradient summed over the blocks and taken back through their
    projections at the end; the input's gradient is what each block's
    queries and residual give plus what the keys and values give."""
    pos = jnp.arange(x.shape[0], dtype=jnp.int32)
    kv = _kv(lp, x, shape, dtype)
    grads, d_kv, d_x = jax.tree.map(jnp.zeros_like, lp), jax.tree.map(jnp.zeros_like, kv), []
    for lo, hi in _blocks(x.shape[0], block):
        g_lp, g_kv, g_x = _attending_block_back(
            lp, kv, x[lo:hi], pos[lo:hi], pos, bias, None if choice is None else choice[lo:hi],
            d_out[lo:hi], shape, share, dtype)
        grads, d_kv = jax.tree.map(jnp.add, grads, g_lp), jax.tree.map(jnp.add, d_kv, g_kv)
        d_x.append(g_x)
    g_lp, g_x = _kv_back(lp, x, d_kv, shape, dtype)
    return jax.tree.map(jnp.add, grads, g_lp), jnp.concatenate(d_x) + g_x


@partial(jax.jit, static_argnames=("shape", "dtype"))
def _head_block(tail, x, targets, weight, shape, dtype):
    return jax.value_and_grad(
        lambda tail, x: nll_sum(head_logits(tail, x, shape, dtype), targets, weight),
        argnums=(0, 1))({"norm": tail["norm"], "head": tail["head"]}, x)


def tail_loss_and_grads(params, batch, shape: Shape, share: Share, bias=None, choice=None,
                        block: int = 1024, dtype=None):
    """(loss, gradients of ``tail_of(params)``) of a batch at its timed
    size: the layers before the tail forward only; the final norm and the
    head in blocks of positions; the tail's layers from the last back, each
    from the gradient of its output: an attention layer in blocks of
    queries (``_attending_layer_back``), a delta-rule layer's MLP in blocks
    and its mixer the whole sequence at once (``_delta_layer_back``). The
    earlier layers' gradients are not computed."""
    targets, weight = targets_of(batch)
    count = float(weight.sum())
    n_layers = n_expert_layers(params)
    first = max(n_layers - TAIL_LAYERS, 0)
    if bias is None:
        bias = jnp.zeros((n_layers, shape.routed), jnp.float32)
    # the tail's layers whole (a layer is computed with all its weights), its gradients pruned at the end
    tail = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32),
                        {"layers": expert_layers(params, start=first), "norm": params["norm"],
                         "head": params["head"]})
    grads = jax.tree.map(jnp.zeros_like, tail)
    # the stream the tail reads, every sequence; the weights before the tail are then let go
    entering = [hidden_states(params, batch[s], shape, share, bias,
                              None if choice is None else choice[s], block, dtype, upto=first)[0]
                for s in range(batch.shape[0])]
    del params
    total = 0.0
    for s, x in enumerate(entering):
        ch = None if choice is None else choice[s]
        follow = [None if ch is None else ch[first + i] for i in range(len(tail["layers"]))]
        streams = [x]  # what each of the tail's layers reads, then what the head reads
        for i, lp in enumerate(tail["layers"]):
            streams.append(_layer(lp, streams[-1], bias[first + i], follow[i],
                                  shape, share, dtype, block)[0])
        d_out = []
        for lo, hi in _blocks(x.shape[0], block):
            value, (g_head, g_x) = _head_block(tail, streams[-1][lo:hi], targets[s, lo:hi],
                                               weight[s, lo:hi] / count, shape, dtype)
            total = total + value
            d_out.append(g_x)
            for name in ("norm", "head"):
                grads[name] = grads[name] + g_head[name]
        d_out = jnp.concatenate(d_out)
        for i in reversed(range(len(tail["layers"]))):
            lp = tail["layers"][i]
            back = _delta_layer_back if "a_log" in lp else _attending_layer_back
            g_lp, d_out = back(lp, streams[i], bias[first + i], follow[i], d_out, shape, share,
                               dtype, block if "a_log" in lp else min(block, BACK_BLOCK))
            grads["layers"][i] = jax.tree.map(jnp.add, grads["layers"][i], g_lp)
    grads["layers"] = _pruned(grads["layers"])
    return total, grads


# ---- the optimizer, for the steps the reference follows

def adam_step(p, g, m, v, step: int, learn_rate: float, weight_decay: float,
              beta1: float = 0.9, beta2: float = 0.999, epsilon: float = 1e-9, warmup: int = 0):
    """Adam as the configuration assumes it (the decay folded into the
    gradient, bias-corrected moments, the learn rate rising linearly over
    the first ``warmup`` steps), one leaf, float64 on the host. ``step``
    counts from 1. Returns (p, m, v): the weight in float64, the moments as
    the program keeps them between steps, in float32 (at the cell's size
    the tail's three trees in float64 are 7.4 GB of the host's memory, and
    the check holds two generations of them)."""
    p, g, m, v = (np.asarray(a, np.float64) for a in (p, g, m, v))
    g = g + weight_decay * p
    m = beta1 * m + (1.0 - beta1) * g
    v = beta2 * v + (1.0 - beta2) * g * g
    if warmup > 0:
        learn_rate = learn_rate * min(1.0, step / warmup)
    rate = learn_rate * np.sqrt(1.0 - beta2 ** step) / (1.0 - beta1 ** step)
    return p - rate * m / (np.sqrt(v) + epsilon), m.astype(np.float32), v.astype(np.float32)
