"""NN pieces of the token-sequence family (models/seqlm.py): RMS norm (its
weight as it is, or zero-centred: ``1 + w``), rotary positions (over a
whole head or its leading dims), SwiGLU, normal initialisation and, for
the delta-rule mixer, a depthwise causal convolution over positions, an L2
norm per head and a gated RMS norm (the gate through sigmoid or SiLU).

``causal_conv`` and ``l2_norm`` are the arithmetic of the mixer's q, k and
v and take what they are given: a whole ``[B, S, C]`` array (a test, a
reference), or one tile of one head with the rows before it, a value in
VMEM, which is how the mixer itself runs them (ops/conv_operand.py's
kernels call them by these names, tile by tile, and nothing of theirs
passes through HBM in float32). So they keep to what both XLA and Mosaic
lower: a pad, static slices, a column of the taps, a sum over lanes.

Numeric policy: norms and rotations in float32 whatever the compute dtype;
a matrix product reads its operands through ``cast`` (nn/layers.py's
``compute_cast``: bfloat16 under PRECISION:bfloat16, float32 masters
otherwise) and accumulates in float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def normal_init(key: jax.Array, shape, std: float = 0.02) -> jax.Array:
    return jax.random.normal(key, shape, jnp.float32) * std


def rms_norm(x: jax.Array, weight: jax.Array, eps: float) -> jax.Array:
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * weight


def centred_rms_norm(x: jax.Array, weight: jax.Array, eps: float) -> jax.Array:
    """The zero-centred RMS norm: ``x_hat * (1 + weight)``, a weight of
    zero being the plain normalisation (it starts there)."""
    return rms_norm(x, 1.0 + weight, eps)


def l2_norm(x: jax.Array, eps: float = 1e-6) -> jax.Array:
    """``x / |x|`` over the last axis, float32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + eps)


def gated_rms_norm(x: jax.Array, weight: jax.Array, gate: jax.Array, eps: float,
                   activation=jax.nn.sigmoid) -> jax.Array:
    """``rms_norm(x) * activation(gate)``, float32: the gate through a
    sigmoid (KDA) or SiLU (the per-head-gated delta rule)."""
    return rms_norm(x, weight, eps) * activation(gate.astype(jnp.float32))


def sigmoid_gate(x: jax.Array, gate: jax.Array) -> jax.Array:
    """``x * sigmoid(gate)``, float32: an attention's output gate."""
    return x.astype(jnp.float32) * jax.nn.sigmoid(gate.astype(jnp.float32))


def causal_conv(x: jax.Array, weight: jax.Array) -> jax.Array:
    """Depthwise causal convolution over the positions (axis 1) of ``x [B,
    S, C]`` (any float dtype) with the taps ``weight [C, K]`` float32, one
    row of ``K`` a channel; float32 ``[B, S, C]``: channel ``c`` of
    position ``t`` is ``sum_i weight[c, i] * x[t - (K - 1) + i, c]``, zeros
    before position 0. A position reads itself and the ``K - 1`` before it
    in its own sequence, nothing later and nothing of another sequence.
    The mixer gives it a tile's rows behind their halo (``[1, HALO + tile,
    d]``, one head's ``d`` channels and their ``[d, K]`` taps) and drops
    the halo's rows from the result, so the zeros stand only where a
    sequence starts."""
    taps, positions = weight.shape[-1], x.shape[1]
    padded = jnp.pad(x.astype(jnp.float32), ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(padded[:, i: i + positions] * weight[:, i] for i in range(taps))


def rotary(x: jax.Array, pos: jax.Array, theta: float) -> jax.Array:
    """``x [..., S, d]`` turned by the positions ``pos [S]``; dimension
    ``i`` pairs with ``i + d/2``."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x = x.astype(jnp.float32)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def rotary_leading(x: jax.Array, pos: jax.Array, theta: float, dims: int) -> jax.Array:
    """``x [..., S, d]`` float32 with its leading ``dims`` dimensions turned
    by ``rotary`` (``i`` pairs with ``i + dims/2``, frequencies ``theta^(-2i
    / dims)``) and the rest as they are: a partial rotary factor."""
    return jnp.concatenate([rotary(x[..., :dims], pos, theta),
                            x[..., dims:].astype(jnp.float32)], axis=-1)


def matmul(a: jax.Array, b: jax.Array, cast, out_dtype=jnp.float32) -> jax.Array:
    """``cast(a) @ cast(b)`` accumulated in float32, held as ``out_dtype``.
    Float32 operands (no compute dtype) multiply at the highest precision:
    on a TPU a float32 product is otherwise rounded to bfloat16 passes."""
    a, b = cast(a), cast(b)
    precision = jax.lax.Precision.HIGHEST if a.dtype == jnp.float32 else None
    return jnp.matmul(a, b, precision=precision,
                      preferred_element_type=jnp.float32).astype(out_dtype)


def swiglu(h: jax.Array, wg: jax.Array, wu: jax.Array, wd: jax.Array, cast) -> jax.Array:
    """``(silu(h Wg) * h Wu) Wd``; the hidden activations are held in the
    compute dtype, the result in float32."""
    h = cast(h)
    g = matmul(h, wg, cast, h.dtype)
    u = matmul(h, wu, cast, h.dtype)
    return matmul(jax.nn.silu(g) * u, wd, cast)
