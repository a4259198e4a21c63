"""Plain reference of the hybrid stack Kimi-Linear-48B-A3B configures:
float32 ``jax.numpy``, every matrix product at
``jax.default_matmul_precision("highest")``, no kernel, no chunked form,
nothing of the program's and nothing of ``reference/moonlight.py``'s (whose
rotary this stack must not share by accident). ``x`` is the residual
stream ``[T, hidden]`` of ONE sequence of ``T`` positions; a batch is a
loop over its sequences.

A layer is a token mixer plus an MLP. Which mixer a layer has is read from
its weights (a layer with ``wkv_a`` attends, one with ``wf_a`` is a KDA
layer); the first layer's MLP is dense, every later one routes.

**KDA (Kimi Delta Attention)**, ``h = rms(x)``, heads of ``d = 128``:

1. ``q~ = h Wq``, ``k~ = h Wk``, ``v~ = h Wv``; each channel through its
   own causal convolution over positions, ``y_t = sum_{i=0..K-1} w[:, i]
   z_{t-(K-1)+i}`` (zeros before the sequence's start), written out as the
   sum of its ``K`` shifted terms, then SiLU; per head ``q = q / |q| *
   d^-0.5``, ``k = k / |k|`` (``|.|`` as ``sqrt(sum squares + 1e-6)``).
2. ``a = (h Wf_down) Wf_up``; ``g = -exp(A_log[head]) * softplus(a +
   dt_bias)`` per head and key channel; ``beta = sigmoid(h Wb)`` per head.
3. Per head a state ``S [d, d]``, zero at the sequence's start, **position
   by position** (a ``lax.scan`` over positions; it is cut into segments
   under ``jax.checkpoint`` so that a gradient keeps a state per segment
   and not per position, which is bookkeeping and no other formula):
   ``S' = Diag(exp(g_t)) S``; ``S = S' + beta_t k_t (v_t - S'^T k_t)^T``;
   ``o_t = S^T q_t``.
4. ``z = (h Wg_down) Wg_up``; per head ``y = rms(o; w[d]) * sigmoid(z)``;
   ``x += concat(heads) Wo``.

**Latent attention without positions** (``mla_use_nope``): ``q = h Wq`` ->
heads of ``[q_nope | q_shared]``; ``[c | k_shared] = h Wkv_a``; ``c =
rms(c)``; ``[k_nope | v]`` per head ``= c Wkv_b``; score ``(q_nope.k_nope +
q_shared.k_shared) / sqrt(nope + shared)``: the 64 shared dims stay in the
score and nothing is rotated; position ``i`` sees every ``j <= i``;
softmax; aggregate ``v``; ``x += concat(heads) Wo``.

**MLPs** as the DeepSeek-V3 block has them: the dense SwiGLU; the expert
layer ``s = sigmoid(h Wr)`` (``num_experts`` wide, float32), the
``num_experts_per_token`` largest of ``s + b`` (``b`` a buffer, given),
``w = s[chosen] / (sum + 1e-20) * routed_scaling_factor``, ``x += sum_k w_k
E_k(h) + S(h)``. Then the final ``rms``, the head, the mean next-token
cross-entropy (a sequence's last position has no target).

One chip's share (``Share``): the experts ``first .. first + held`` of every
layer and a slice of the vocabulary; the router keeps its width, the
weights are normalised over all chosen experts, the routed sum runs over
the held ones; what absent experts would add is left out, and that partial
result goes on to the next layer. ``held = num_experts`` is the uncut layer.

Departures from the published description, all stated: no bias anywhere but
``dt_bias`` (the published code may carry one on ``Wg_up``, 4,096
parameters the config's keys do not show); ``num_expert_group`` and
``topk_group`` are 1, so grouped top-k is the plain top-k; no auxiliary
loss; the correction bias is what it is given; no state or attention reset
at document boundaries; the L2 norm's epsilon (1e-6) is assumed.

``choice`` (optional, ``[layers with experts, T, k]``): the reference then
*follows* that choice of experts, the weights still from its own scores.
``dtype`` (the control's alone): every matrix product, the recurrence's
included, then reads its operands as that dtype would hold them.

The weights come in the layout the program keeps them in: ``embed``,
``dense`` (the first layer), the expert layers in runs of one mixer kind
stacked on a leading axis under ``moe``, ``moe1``, ``moe2``, ... in the
stack's order, ``norm``, ``head``. A layer's leaves:

    KDA:  norm1, wq, wk, wv [hidden, H d], cq, ck, cv [H d, K], wf_a
          [hidden, d], wf_b [d, H d], a_log [H], dt_bias [H d], wb [hidden,
          H], wz_a, wz_b (the output gate's pair), o_norm [d], wo, norm2
    latent attention: norm1, wq, wkv_a, kv_norm, wkv_b, wo, norm2
    dense: wg, wu, wd;  experts: router, eg, eu, ed, sg, su, sd
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
    kv_rank: int
    nope: int
    shared: int
    v_head: int
    routed: int
    per_token: int
    scale: float
    eps: float
    kda_heads: int
    kda_dim: int

    @staticmethod
    def of(model: dict) -> "Shape":
        linear = model["linear_attn_config"]
        return Shape(
            hidden=int(model["hidden_size"]), heads=int(model["num_attention_heads"]),
            kv_rank=int(model["kv_lora_rank"]), nope=int(model["qk_nope_head_dim"]),
            shared=int(model["qk_rope_head_dim"]), v_head=int(model["v_head_dim"]),
            routed=int(model["num_experts"]), per_token=int(model["num_experts_per_token"]),
            scale=float(model["routed_scaling_factor"]), eps=float(model["rms_norm_eps"]),
            kda_heads=int(linear["num_heads"]), kda_dim=int(linear["head_dim"]),
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


def rms(x, weight, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * weight


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


def delta_rule_tokens(q, k, v, g, beta, segment: int = SEGMENT):
    """``o [T, H, dv]`` of the recurrence, position by position, from ``q,
    k, g [T, H, dk]``, ``v [T, H, dv]`` and ``beta [T, H]``."""
    t, h, dk = k.shape

    def position(state, at):
        q_t, k_t, v_t, g_t, b_t = at
        with jax.default_matmul_precision("highest"):
            decayed = jnp.exp(g_t)[:, :, None] * state
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


@partial(jax.checkpoint, static_argnums=(3, 4, 5, 6))
def _operand(h, w, taps, heads, d, scale, dtype):
    """One of q, k, v ``[T, H, d]`` from the normed stream: product,
    convolution, SiLU and, with a ``scale``, unit length per head times it.
    Under ``jax.checkpoint`` (as ``_gates`` and ``_gated_output``): a
    gradient keeps the path's inputs and walks it again, which bounds the
    memory of a whole sequence's backward and changes no formula."""
    t = jax.nn.silu(short_conv(_mm(h, w, dtype), taps)).reshape(h.shape[0], heads, d)
    return t if scale is None else unit(t) * scale


@partial(jax.checkpoint, static_argnums=(6, 7, 8))
def _gates(h, wf_a, wf_b, a_log, dt_bias, wb, heads, d, dtype):
    """(log-decay ``g [T, H, d]``, write strength ``beta [T, H]``)."""
    a = _mm(_mm(h, wf_a, dtype), wf_b, dtype) + dt_bias
    g = -jnp.exp(a_log)[:, None] * jax.nn.softplus(a.reshape(h.shape[0], heads, d))
    return g, jax.nn.sigmoid(_mm(h, wb, dtype))


@partial(jax.checkpoint, static_argnums=(6, 7))
def _gated_output(h, o, wz_a, wz_b, o_norm, wo, eps, dtype):
    t, heads, d = o.shape
    z = _mm(_mm(h, wz_a, dtype), wz_b, dtype).reshape(t, heads, d)
    return _mm((rms(o, o_norm, eps) * jax.nn.sigmoid(z)).reshape(t, heads * d), wo, dtype)


def kda_mixer(lp, x, shape: Shape, dtype=None):
    """``x`` (a whole sequence) plus its KDA."""
    heads, d = shape.kda_heads, shape.kda_dim
    h = rms(x, lp["norm1"], shape.eps)
    q = _operand(h, lp["wq"], lp["cq"], heads, d, d ** -0.5, dtype)
    k = _operand(h, lp["wk"], lp["ck"], heads, d, 1.0, dtype)
    v = _operand(h, lp["wv"], lp["cv"], heads, d, None, dtype)
    g, beta = _gates(h, lp["wf_a"], lp["wf_b"], lp["a_log"], lp["dt_bias"], lp["wb"], heads, d, dtype)
    o = delta_rule_tokens(_held_in(q, dtype), _held_in(k, dtype), _held_in(v, dtype), g, beta)
    return x + _gated_output(h, o, lp["wz_a"], lp["wz_b"], lp["o_norm"], lp["wo"], shape.eps, dtype)


def keys_values(lp, x, shape: Shape, dtype=None):
    """(k_nope [H, T, nope], k_shared [T, shared], v [H, T, v_head]) of a
    whole sequence; nothing is rotated."""
    h = rms(x, lp["norm1"], shape.eps)
    cks = _mm(h, lp["wkv_a"], dtype)
    c = rms(cks[:, : shape.kv_rank], lp["kv_norm"], shape.eps)
    kv = _mm(c, lp["wkv_b"], dtype).reshape(x.shape[0], shape.heads, shape.nope + shape.v_head)
    kv = jnp.swapaxes(kv, 0, 1)
    return kv[..., : shape.nope], cks[:, shape.kv_rank:], kv[..., shape.nope:]


def attend(lp, x_q, pos_q, k_nope, k_shared, v, pos_k, shape: Shape, dtype=None):
    """``x_q`` (a block of queries of the sequence) plus its attention over
    the sequence's keys and values."""
    h = rms(x_q, lp["norm1"], shape.eps)
    q = _mm(h, lp["wq"], dtype).reshape(x_q.shape[0], shape.heads, shape.nope + shape.shared)
    q = jnp.swapaxes(q, 0, 1)
    with jax.default_matmul_precision("highest"):
        score = (
            jnp.einsum("hqd,hkd->hqk", _held_in(q[..., : shape.nope], dtype), _held_in(k_nope, dtype))
            + jnp.einsum("hqd,kd->hqk", _held_in(q[..., shape.nope:], dtype), _held_in(k_shared, dtype))
        ) / np.sqrt(shape.nope + shape.shared)
        score = jnp.where(pos_k[None, None, :] <= pos_q[None, :, None], score, -jnp.inf)
        p = jax.nn.softmax(score, axis=-1)
        out = jnp.einsum("hqk,hkd->qhd", _held_in(p, dtype), _held_in(v, dtype))
    return x_q + _mm(out.reshape(x_q.shape[0], -1), lp["wo"], dtype)


def mixer(lp, x, shape: Shape, dtype=None, block: Optional[int] = None):
    """``x`` plus the layer's token mixer, whichever its weights name; the
    latent attention in blocks of ``block`` queries (None: all at once)."""
    if "wf_a" in lp:
        return kda_mixer(lp, x, shape, dtype)
    pos = jnp.arange(x.shape[0], dtype=jnp.int32)
    kv = keys_values(lp, x, shape, dtype)
    return jnp.concatenate([
        attend(lp, x[lo:hi], pos[lo:hi], *kv, pos, shape, dtype)
        for lo, hi in _blocks(x.shape[0], block or x.shape[0])
    ])


def dense_mlp(lp, x, shape: Shape, dtype=None):
    return x + swiglu(rms(x, lp["norm2"], shape.eps), lp["wg"], lp["wu"], lp["wd"], dtype)


def router_scores(lp, h):
    """``sigmoid(h Wr)`` in float32 whatever ``dtype`` the rest is held in."""
    return jax.nn.sigmoid(_mm(h, lp["router"], None))


def choose(scores, bias, per_token: int):
    return lax.top_k(scores + bias, per_token)[1]


def expert_parts(lp, x, bias, shape: Shape, share: Share, choice=None, dtype=None):
    """(routed part of the held experts, shared part, the reference's own
    choice) of the expert layer at ``x``; ``choice`` given, the routed part
    follows it."""
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
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, targets[:, None], axis=-1)[:, 0] * weight)


# ---- the stack

def n_expert_layers(params) -> int:
    runs = [v for k, v in params.items() if k.startswith("moe")]
    return sum(int(run["router"].shape[0]) for run in runs)


def expert_layers(params, upto: Optional[int] = None, start: int = 0) -> List[Dict[str, Any]]:
    """The expert layers ``start .. upto`` (None: to the last), unstacked,
    in the stack's order: the runs ``moe``, ``moe1``, ``moe2``, ... one
    after the other. Only the layers asked for are taken out of their run
    (each is a copy)."""
    upto = n_expert_layers(params) if upto is None else upto
    out, i, at = [], 0, 0
    while f"moe{i or ''}" in params:
        run = params[f"moe{i or ''}"]
        for n in range(run["router"].shape[0]):
            if start <= at < upto:
                out.append(jax.tree.map(lambda a: a[n], run))
            at += 1
        i += 1
    return out


def _blocks(total: int, block: int):
    return [(lo, min(lo + block, total)) for lo in range(0, total, block)]


@partial(jax.jit, static_argnames=("shape", "dtype", "block"))
def _dense_layer(lp, x, shape, dtype, block):
    return dense_mlp(lp, mixer(lp, x, shape, dtype, block), shape, dtype)


@partial(jax.jit, static_argnames=("shape", "share", "dtype", "block"))
def _expert_layer(lp, x, bias, choice, shape, share, dtype, block):
    return expert_mlp(lp, mixer(lp, x, shape, dtype, block), bias, shape, share, choice, dtype)


def hidden_states(params, tokens, shape: Shape, share: Share, bias=None, choice=None,
                  block: int = 1024, dtype=None, upto: Optional[int] = None):
    """(the residual stream ``[T, hidden]`` after the last layer, the
    reference's own choice of experts ``[L, T, k]`` at the stream it
    computed, which follows ``choice`` where one is given) of one sequence
    ``tokens [T]``. ``upto``: stop before expert layer ``upto`` (the stream
    that layer reads)."""
    layers = expert_layers(params, upto)
    if bias is None:
        bias = jnp.zeros((len(layers), shape.routed), jnp.float32)
    x = jnp.asarray(params["embed"], jnp.float32)[jnp.asarray(tokens)]
    x = _dense_layer(params["dense"], x, shape, dtype, block)
    choices = []
    for i, lp in enumerate(layers):
        x, own = _expert_layer(lp, x, bias[i], None if choice is None else choice[i],
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
        x = dense_mlp(params["dense"], mixer(params["dense"], x, shape, dtype), shape, dtype)
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


TAIL_LAYERS = 2  # the last expert layers whose gradients tail_loss_and_grads gives
BACK_BLOCK = 256  # queries a block of the latent attention's backward, at most


def tail_of(params) -> Dict[str, Any]:
    """The weights ``tail_loss_and_grads`` differentiates: the last
    ``TAIL_LAYERS`` expert layers (at the benchmark's cut the
    latent-attention layer and the KDA layer after it: one of each mixer),
    the final norm and the head."""
    n = n_expert_layers(params)
    return {"layers": expert_layers(params, start=max(n - TAIL_LAYERS, 0)), "norm": params["norm"],
            "head": params["head"]}


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


def _layer_back(lp, x, bias, choice, d_out, shape, share, dtype, block):
    """(gradient of the layer's weights, of its input) from the gradient of
    its output: the expert MLP block of positions by block, then the mixer
    the whole sequence at once: a KDA layer's memory is linear in the
    positions; a latent-attention layer holds its ``[heads, T, T]``
    probabilities, which fits a small size only."""
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
def _latent_block_back(lp, kv, x_q, pos_q, pos_k, bias, choice, d_out, shape, share, dtype):
    _, back = jax.vjp(
        lambda lp, kv: expert_mlp(lp, attend(lp, x_q, pos_q, *kv, pos_k, shape, dtype), bias, shape,
                                  share, choice, dtype)[0],
        lp, kv)
    return back(d_out)


@partial(jax.jit, static_argnames=("shape", "dtype"))
def _kv(lp, x, shape, dtype):
    return keys_values(lp, x, shape, dtype)


@partial(jax.jit, static_argnames=("shape", "dtype"))
def _kv_back(lp, x, d_kv, shape, dtype):
    _, back = jax.vjp(lambda lp: keys_values(lp, x, shape, dtype), lp)
    return back(d_kv)[0]


def _latent_layer_back(lp, x, bias, choice, d_out, shape, share, dtype, block):
    """Gradient of a latent-attention expert layer's weights from the
    gradient of its output, block of queries by block of queries: the keys
    and values of the sequence are made once, their gradient summed over
    the blocks and taken back through their projections at the end."""
    pos = jnp.arange(x.shape[0], dtype=jnp.int32)
    kv = _kv(lp, x, shape, dtype)
    grads, d_kv = jax.tree.map(jnp.zeros_like, lp), jax.tree.map(jnp.zeros_like, kv)
    for lo, hi in _blocks(x.shape[0], block):
        g_lp, g_kv = _latent_block_back(
            lp, kv, x[lo:hi], pos[lo:hi], pos, bias, None if choice is None else choice[lo:hi],
            d_out[lo:hi], shape, share, dtype)
        grads, d_kv = jax.tree.map(jnp.add, grads, g_lp), jax.tree.map(jnp.add, d_kv, g_kv)
    return jax.tree.map(jnp.add, grads, _kv_back(lp, x, d_kv, shape, dtype))


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
    from the gradient of its output: the MLP in blocks, the mixer the whole
    sequence at once (``_layer_back``), but a latent-attention layer that is not the last in
    blocks of queries (``_latent_layer_back``; nothing below it needs its
    input's gradient). The earlier layers' gradients are not computed."""
    targets, weight = targets_of(batch)
    count = float(weight.sum())
    n_layers = n_expert_layers(params)
    first = max(n_layers - TAIL_LAYERS, 0)
    if bias is None:
        bias = jnp.zeros((n_layers, shape.routed), jnp.float32)
    tail = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), tail_of(params))
    grads = jax.tree.map(jnp.zeros_like, tail)
    # the stream the tail reads, every sequence; the weights before the tail are then let go
    # (at the cell's size they are 1.4 GB of the little that is free beside the program's state)
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
            streams.append(_expert_layer(lp, streams[-1], bias[first + i], follow[i],
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
            if "wkv_a" in lp and i < len(tail["layers"]) - 1:
                g_lp = _latent_layer_back(lp, streams[i], bias[first + i], follow[i], d_out,
                                          shape, share, dtype, min(block, BACK_BLOCK))
            else:
                g_lp, d_out = _layer_back(lp, streams[i], bias[first + i], follow[i], d_out,
                                          shape, share, dtype, block)
            grads["layers"][i] = jax.tree.map(jnp.add, grads["layers"][i], g_lp)
    return total, grads


# ---- the optimizer, for the steps the reference follows

def adam_step(p, g, m, v, step: int, learn_rate: float, weight_decay: float,
              beta1: float = 0.9, beta2: float = 0.999, epsilon: float = 1e-9, warmup: int = 0):
    """Adam as the configuration assumes it (the decay folded into the
    gradient, bias-corrected moments, the learn rate rising linearly over
    the first ``warmup`` steps), one leaf, float64 on the host. ``step``
    counts from 1. Returns (p, m, v)."""
    p, g, m, v = (np.asarray(a, np.float64) for a in (p, g, m, v))
    g = g + weight_decay * p
    m = beta1 * m + (1.0 - beta1) * g
    v = beta2 * v + (1.0 - beta2) * g * g
    if warmup > 0:
        learn_rate = learn_rate * min(1.0, step / warmup)
    rate = learn_rate * np.sqrt(1.0 - beta2 ** step) / (1.0 - beta1 ** step)
    return p - rate * m / (np.sqrt(v) + epsilon), m, v
