"""NN pieces of the token-sequence family (models/seqlm.py): RMS norm,
rotary positions, SwiGLU, normal initialisation.

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
