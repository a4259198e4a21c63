"""NN pieces of the token-sequence family (models/seqlm.py): RMS norm,
rotary positions, SwiGLU, normal initialisation and, for the delta-rule
mixer, a depthwise causal convolution over positions, an L2 norm per head
and a sigmoid-gated RMS norm.

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


def l2_norm(x: jax.Array, eps: float = 1e-6) -> jax.Array:
    """``x / |x|`` over the last axis, float32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + eps)


def gated_rms_norm(x: jax.Array, weight: jax.Array, gate: jax.Array, eps: float) -> jax.Array:
    """``rms_norm(x) * sigmoid(gate)``, float32."""
    return rms_norm(x, weight, eps) * jax.nn.sigmoid(gate.astype(jnp.float32))


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
