"""Plain reference of the DeepSeek-V3 block as Moonlight-16B-A3B configures
it: float32 ``jax.numpy``, every matrix product at
``jax.default_matmul_precision("highest")``, no kernel, no recomputation,
nothing of the program's. ``x`` is the residual stream ``[T, hidden]`` of
ONE sequence of ``T`` positions; a batch is a loop over its sequences.

Attention (latent, ``q_lora_rank`` null: the query is not compressed):
``h = rms(x)``; ``q = h Wq`` -> ``heads`` of ``[q_nope | q_rope]``;
``[c | k_rope] = h Wkv_a`` (``kv_lora_rank`` + rope dims);
``c = rms(c)``; ``[k_nope | v]`` per head ``= c Wkv_b``; rotary
(``rope_theta``) on ``q_rope`` and on the one ``k_rope`` all heads share;
score ``(q_nope.k_nope + q_rope.k_rope) / sqrt(nope + rope)``, position
``i`` sees every ``j <= i`` of its sequence, softmax per position,
aggregate ``v``; ``x += concat(heads) Wo``.

Dense MLP (the first ``first_k_dense_replace`` layers):
``x += Wd (silu(Wg h) * Wu h)``, ``h = rms(x)``.

Expert layer: ``s = sigmoid(h Wr)`` (``n_routed_experts`` wide); choose the
``num_experts_per_tok`` largest of ``s + b`` (``b`` the ``noaux_tc``
correction bias: a buffer, no gradient); ``w = s[chosen] / (sum + 1e-20) *
routed_scaling_factor``; ``x += sum_k w_k E_k(h) + S(h)``, ``E_k`` a SwiGLU
of ``moe_intermediate_size``, ``S`` one SwiGLU of ``n_shared_experts`` times
that width. Then the final ``rms``, the head ``[hidden, vocab]``, and the
mean next-token cross-entropy (the last position of a sequence has no
target).

One chip's share (``Share``): the experts ``first .. first + held`` of every
layer and a slice of the vocabulary. The router keeps its published width
and its experts per token; the weights ``w`` are normalised over all the
chosen experts, held or not; the routed sum runs over the held ones only.
What absent experts would add is left out, and that partial result goes on
to the next layer. ``held = n_routed_experts`` is the uncut layer.

Departures from the published code, all stated: the rotary pairs dimension
``i`` with ``i + rope/2`` (the published code first de-interleaves
``q_rope`` / ``k_rope``, a fixed permutation of the columns of ``Wq`` and
``Wkv_a``, which seeded random weights do not tell apart); ``n_group`` and
``topk_group`` are 1, so group-limited routing is the plain top-k; no
auxiliary loss; the correction bias is what it is given (the
configuration fixes it at zero).

``choice`` (optional, ``[layers with experts, T, k]``): the reference then
*follows* that choice of experts, the weights still from its own scores.
The check passes the program's choice where it compares logits, loss and
gradients, so that a near-tie in the router, which rounding decides, does
not masquerade as an error of the experts; ``route_mismatch`` compares the
choices themselves.

``dtype`` (the control's alone, benchmark/control.py): every matrix
product then reads its operands as that dtype would hold them.

The weights come in the layout the program keeps them in (a dict; the
expert layers stacked on a leading axis):

    embed [vocab, hidden]; norm [hidden]; head [hidden, vocab]
    dense, moe: norm1, wq, wkv_a, kv_norm, wkv_b, wo, norm2 and
      dense: wg, wu [hidden, ffn], wd [ffn, hidden]
      moe:   router [hidden, routed]; eg, eu [held, hidden, width],
             ed [held, width, hidden]; sg, su, sd (the shared SwiGLU)
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


class Shape(NamedTuple):
    """The published sizes the equations need (``config.json``'s keys)."""

    hidden: int
    heads: int
    kv_rank: int
    nope: int
    rope: int
    v_head: int
    routed: int
    per_token: int
    scale: float
    theta: float
    eps: float

    @staticmethod
    def of(model: dict) -> "Shape":
        return Shape(
            hidden=int(model["hidden_size"]), heads=int(model["num_attention_heads"]),
            kv_rank=int(model["kv_lora_rank"]), nope=int(model["qk_nope_head_dim"]),
            rope=int(model["qk_rope_head_dim"]), v_head=int(model["v_head_dim"]),
            routed=int(model["n_routed_experts"]), per_token=int(model["num_experts_per_tok"]),
            scale=float(model["routed_scaling_factor"]), theta=float(model["rope_theta"]),
            eps=float(model["rms_norm_eps"]),
        )


class Share(NamedTuple):
    """Which routed experts are held here: ``first .. first + held``."""

    first: int
    held: int


# ---- the pieces

def _held_in(x, dtype):
    """``x`` as ``dtype`` would hold it, in float32 again (None: as it is).
    The control alone names a dtype. As reference/gcn.py holds it: scaled
    by a power of two so that the largest entry sits near the top of the
    dtype's range (an 8-bit path scales what it stores), rounded by
    ``reduce_precision`` to the dtype's exponent and mantissa bits (a cast
    there and back is one the TPU's compiler may leave out), scaled back;
    a gradient passes through unrounded."""
    if dtype is None:
        return x
    info = jnp.finfo(dtype)
    top = jnp.max(jnp.abs(x)) / float(2.0 ** (info.maxexp - 2))  # e4m3: entries up to 128
    scale = jnp.exp2(jnp.ceil(jnp.log2(jnp.where(top > 0, top, 1.0))))
    held = jax.lax.reduce_precision(
        x / scale, exponent_bits=info.nexp, mantissa_bits=info.nmant) * scale
    return x + jax.lax.stop_gradient(held - x)


def _mm(a, b, dtype):
    with jax.default_matmul_precision("highest"):
        return jnp.matmul(_held_in(a, dtype), _held_in(b, dtype))


def rms(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * weight


def rotary(x, pos, theta):
    """``x [..., T, d]`` turned by its positions ``pos [T]``: dimension
    ``i`` pairs with ``i + d/2``."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def swiglu(h, wg, wu, wd, dtype=None):
    return _mm(jax.nn.silu(_mm(h, wg, dtype)) * _mm(h, wu, dtype), wd, dtype)


def keys_values(lp, x, pos, shape: Shape, dtype=None):
    """(k_nope [H, T, nope], k_rope [T, rope], v [H, T, v_head]) of a whole
    sequence."""
    h = rms(x, lp["norm1"], shape.eps)
    ckr = _mm(h, lp["wkv_a"], dtype)
    c = rms(ckr[:, : shape.kv_rank], lp["kv_norm"], shape.eps)
    k_rope = rotary(ckr[:, shape.kv_rank:], pos, shape.theta)
    kv = _mm(c, lp["wkv_b"], dtype).reshape(x.shape[0], shape.heads, shape.nope + shape.v_head)
    kv = jnp.swapaxes(kv, 0, 1)
    return kv[..., : shape.nope], k_rope, kv[..., shape.nope:]


def attend(lp, x_q, pos_q, k_nope, k_rope, v, pos_k, shape: Shape, dtype=None):
    """``x_q`` (a block of queries of the sequence) plus its attention over
    the sequence's keys and values."""
    h = rms(x_q, lp["norm1"], shape.eps)
    q = _mm(h, lp["wq"], dtype).reshape(x_q.shape[0], shape.heads, shape.nope + shape.rope)
    q = jnp.swapaxes(q, 0, 1)
    q_nope, q_rope = q[..., : shape.nope], rotary(q[..., shape.nope:], pos_q, shape.theta)
    with jax.default_matmul_precision("highest"):
        score = (
            jnp.einsum("hqd,hkd->hqk", _held_in(q_nope, dtype), _held_in(k_nope, dtype))
            + jnp.einsum("hqd,kd->hqk", _held_in(q_rope, dtype), _held_in(k_rope, dtype))
        ) / np.sqrt(shape.nope + shape.rope)
        score = jnp.where(pos_k[None, None, :] <= pos_q[None, :, None], score, -jnp.inf)
        p = jax.nn.softmax(score, axis=-1)
        out = jnp.einsum("hqk,hkd->qhd", _held_in(p, dtype), _held_in(v, dtype))
    return x_q + _mm(out.reshape(x_q.shape[0], -1), lp["wo"], dtype)


def dense_mlp(lp, x, shape: Shape, dtype=None):
    return x + swiglu(rms(x, lp["norm2"], shape.eps), lp["wg"], lp["wu"], lp["wd"], dtype)


def router_scores(lp, h):
    """``sigmoid(h Wr)`` in float32 whatever ``dtype`` the rest is held in:
    the published code keeps the router in float32."""
    return jax.nn.sigmoid(_mm(h, lp["router"], None))


def choose(scores, bias, per_token: int):
    """Indices ``[T, k]`` of the ``k`` largest of ``scores + bias``."""
    return jax.lax.top_k(scores + bias, per_token)[1]


def expert_parts(lp, x, bias, shape: Shape, share: Share, choice=None, dtype=None):
    """(routed part of the held experts, shared part, the reference's own
    choice) of the expert layer at ``x`` (both parts are added to ``x``).
    ``choice`` given: the routed part follows it; the choice returned is
    still the reference's own, from its own scores at this ``x``."""
    h = rms(x, lp["norm2"], shape.eps)
    scores = router_scores(lp, h)
    own = choose(scores, bias, shape.per_token)
    if choice is None:
        choice = own
    picked = jnp.take_along_axis(scores, choice, axis=-1)
    weight = picked / (picked.sum(axis=-1, keepdims=True) + 1e-20) * shape.scale
    routed = jnp.zeros_like(x)
    for e in range(share.held):
        gate = jnp.sum(jnp.where(choice == share.first + e, weight, 0.0), axis=-1)
        routed = routed + gate[:, None] * swiglu(h, lp["eg"][e], lp["eu"][e], lp["ed"][e], dtype)
    return routed, swiglu(h, lp["sg"], lp["su"], lp["sd"], dtype), own


def expert_mlp(lp, x, bias, shape: Shape, share: Share, choice=None, dtype=None):
    routed, shared, own = expert_parts(lp, x, bias, shape, share, choice, dtype)
    return x + routed + shared, own


def head_logits(params, x, shape: Shape, dtype=None):
    return _mm(rms(x, params["norm"], shape.eps), params["head"], dtype)


def nll_sum(logits, targets, weight):
    """Sum over the positions of ``weight`` times the negative log
    likelihood of ``targets``."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, targets[:, None], axis=-1)[:, 0] * weight)


# ---- one sequence, whole or in blocks of queries

def moe_layer(params, i: int):
    return jax.tree.map(lambda a: a[i], params["moe"])


def n_moe_layers(params) -> int:
    return int(params["moe"]["router"].shape[0])


@partial(jax.jit, static_argnames=("shape", "dtype"))
def _kv(lp, x, pos, shape, dtype):
    return keys_values(lp, x, pos, shape, dtype)


@partial(jax.jit, static_argnames=("shape", "dtype"))
def _dense_block(lp, x_q, pos_q, kv, pos_k, shape, dtype):
    return dense_mlp(lp, attend(lp, x_q, pos_q, *kv, pos_k, shape, dtype), shape, dtype)


@partial(jax.jit, static_argnames=("shape", "share", "dtype"))
def _moe_block(lp, x_q, pos_q, kv, pos_k, bias, choice, shape, share, dtype):
    x = attend(lp, x_q, pos_q, *kv, pos_k, shape, dtype)
    return expert_mlp(lp, x, bias, shape, share, choice, dtype)


def _blocks(total: int, block: int):
    return [(lo, min(lo + block, total)) for lo in range(0, total, block)]


def hidden_states(params, tokens, shape: Shape, share: Share, bias=None, choice=None,
                  block: int = 1024, dtype=None, upto: Optional[int] = None):
    """(the residual stream ``[T, hidden]`` after the last layer, the
    reference's own choice of experts ``[L, T, k]`` at the stream it
    computed, which follows ``choice`` where one is given) of one sequence
    ``tokens [T]``,
    attention and MLPs in blocks of ``block`` queries. ``upto``: stop
    before expert layer ``upto`` (the stream that layer reads)."""
    t = int(tokens.shape[0])
    pos = jnp.arange(t, dtype=jnp.int32)
    n_layers = n_moe_layers(params)
    if bias is None:
        bias = jnp.zeros((n_layers, shape.routed), jnp.float32)
    x = jnp.asarray(params["embed"], jnp.float32)[jnp.asarray(tokens)]
    lp = params["dense"]
    kv = _kv(lp, x, pos, shape, dtype)
    x = jnp.concatenate([
        _dense_block(lp, x[lo:hi], pos[lo:hi], kv, pos, shape, dtype) for lo, hi in _blocks(t, block)
    ])
    choices = []
    for i in range(n_layers if upto is None else upto):
        lp = moe_layer(params, i)
        kv = _kv(lp, x, pos, shape, dtype)
        parts = [
            _moe_block(lp, x[lo:hi], pos[lo:hi], kv, pos, bias[i],
                       None if choice is None else choice[i][lo:hi], shape, share, dtype)
            for lo, hi in _blocks(t, block)
        ]
        x = jnp.concatenate([p[0] for p in parts])
        choices.append(jnp.concatenate([p[1] for p in parts]))
    return x, (jnp.stack(choices) if choices else None)


def targets_of(tokens) -> Tuple[np.ndarray, np.ndarray]:
    """(the next token of every position, 1 where a position has one) of a
    batch ``[sequences, T]``: the last position of a sequence has none."""
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


def logits_at(params, seq_tokens, positions, shape: Shape, share: Share, bias=None,
              choice=None, block: int = 1024, dtype=None):
    """Logits ``[len(positions), vocab]`` of one sequence at ``positions``."""
    x, _ = hidden_states(params, seq_tokens, shape, share, bias, choice, block, dtype)
    return head_logits(params, x[jnp.asarray(positions)], shape, dtype)


# ---- gradients

def whole_loss(params, batch, shape: Shape, share: Share, bias=None, choice=None, dtype=None):
    """The loss as one differentiable expression (no blocks, nothing
    jitted inside): what ``jax.grad`` walks at a small size."""
    targets, weight = targets_of(batch)
    n_layers = n_moe_layers(params)
    if bias is None:
        bias = jnp.zeros((n_layers, shape.routed), jnp.float32)
    total = 0.0
    for s in range(batch.shape[0]):
        pos = jnp.arange(batch.shape[1], dtype=jnp.int32)
        x = params["embed"][jnp.asarray(batch[s])]
        lp = params["dense"]
        x = dense_mlp(lp, attend(lp, x, pos, *keys_values(lp, x, pos, shape, dtype), pos, shape, dtype),
                      shape, dtype)
        for i in range(n_layers):
            lp = moe_layer(params, i)
            x = attend(lp, x, pos, *keys_values(lp, x, pos, shape, dtype), pos, shape, dtype)
            x, _ = expert_mlp(lp, x, bias[i], shape, share,
                              None if choice is None else choice[s][i], dtype)
        total = total + nll_sum(head_logits(params, x, shape, dtype), targets[s], weight[s])
    return total / float(weight.sum())


def loss_and_grads(params, batch, shape: Shape, share: Share, bias=None, choice=None, dtype=None):
    """(loss, gradients in the layout of ``params``) by ``jax.grad`` over
    ``whole_loss``: every weight, at a size where one expression fits."""
    params = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
    return jax.value_and_grad(whole_loss)(params, batch, shape, share, bias, choice, dtype)


def tail_of(params) -> Dict[str, Any]:
    """The weights ``tail_loss_and_grads`` differentiates: the last expert
    layer, the final norm and the head."""
    return {"layer": moe_layer(params, n_moe_layers(params) - 1),
            "norm": params["norm"], "head": params["head"]}


@partial(jax.jit, static_argnames=("shape", "share", "dtype"))
def _tail_block(tail, kv, x_q, pos_q, pos_k, bias, choice, targets, weight, shape, share, dtype):
    def nll(tail, kv):
        x, _ = expert_mlp(tail["layer"], attend(tail["layer"], x_q, pos_q, *kv, pos_k, shape, dtype),
                          bias, shape, share, choice, dtype)
        return nll_sum(head_logits(tail, x, shape, dtype), targets, weight)

    return jax.value_and_grad(nll, argnums=(0, 1))(tail, kv)


@partial(jax.jit, static_argnames=("shape", "dtype"))
def _kv_back(lp, x, pos, d_kv, shape, dtype):
    _, vjp = jax.vjp(lambda lp: keys_values(lp, x, pos, shape, dtype), lp)
    return vjp(d_kv)[0]


def tail_loss_and_grads(params, batch, shape: Shape, share: Share, bias=None, choice=None,
                        block: int = 1024, dtype=None):
    """(loss, gradients of ``tail_of(params)``) of a batch at its timed
    size: the layers before the last expert layer forward only, in blocks;
    the last expert layer, the final norm and the head differentiated block
    of queries by block of queries (the keys and values of the sequence are
    made once, their gradient summed over the blocks and taken back
    through their projections at the end). The earlier layers' gradients
    need every layer's attention probabilities held at once, float32, and
    do not fit beside the program's state: they are not computed."""
    targets, weight = targets_of(batch)
    count = float(weight.sum())
    last = n_moe_layers(params) - 1
    if bias is None:
        bias = jnp.zeros((last + 1, shape.routed), jnp.float32)
    tail = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), tail_of(params))
    grads = jax.tree.map(jnp.zeros_like, tail)
    total = 0.0
    for s in range(batch.shape[0]):
        ch = None if choice is None else choice[s]
        x, _ = hidden_states(params, batch[s], shape, share, bias, ch, block, dtype, upto=last)
        pos = jnp.arange(x.shape[0], dtype=jnp.int32)
        kv = _kv(tail["layer"], x, pos, shape, dtype)
        d_kv = jax.tree.map(jnp.zeros_like, kv)
        for lo, hi in _blocks(x.shape[0], block):
            value, (g_tail, g_kv) = _tail_block(
                tail, kv, x[lo:hi], pos[lo:hi], pos, bias[last],
                None if ch is None else ch[last][lo:hi],
                targets[s, lo:hi], weight[s, lo:hi] / count, shape, share, dtype)
            total = total + value
            grads = jax.tree.map(jnp.add, grads, g_tail)
            d_kv = jax.tree.map(jnp.add, d_kv, g_kv)
        g_layer = _kv_back(tail["layer"], x, pos, d_kv, shape, dtype)
        grads["layer"] = jax.tree.map(jnp.add, grads["layer"], g_layer)
    return total, grads


# ---- the optimizer, for the steps the reference follows

def adam_step(p, g, m, v, step: int, learn_rate: float, weight_decay: float,
              beta1: float = 0.9, beta2: float = 0.999, epsilon: float = 1e-9, warmup: int = 0):
    """Adam as the configuration assumes it (the program's ``nn/param.py``:
    the decay folded into the gradient, bias-corrected moments; the learn
    rate rising linearly over the first ``warmup`` steps), one leaf,
    float64 on the host. ``step`` counts from 1. Returns (p, m, v)."""
    p, g, m, v = (np.asarray(a, np.float64) for a in (p, g, m, v))
    g = g + weight_decay * p
    m = beta1 * m + (1.0 - beta1) * g
    v = beta2 * v + (1.0 - beta2) * g * g
    if warmup > 0:
        learn_rate = learn_rate * min(1.0, step / warmup)
    rate = learn_rate * np.sqrt(1.0 - beta2 ** step) / (1.0 - beta1 ** step)
    return p - rate * m / (np.sqrt(v) + epsilon), m, v
