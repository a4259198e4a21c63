"""One pass over HBM for each of the delta-rule mixer's q, k and v.

Between a KDA layer's product ``[B, S, H * d]`` (the compute dtype) and the
operand ``[B * H, S, d]`` the recurrence reads (ops/delta_rule.py, the
compute dtype) lie a depthwise causal convolution over positions, SiLU, for
queries and keys a unit length per head and a scale, and the change of
layout. All of it is elementwise work, ``taps - 1`` shifted reads along the
positions and one reduction over a head's channels, so it is one Pallas
kernel: a grid step takes ``TILE`` positions of one head of one sequence
(a ``[TILE, d]`` column block of the product, the ``HALO`` rows before it
beside it), does the arithmetic in float32 in VMEM by the functions of
nn/seq.py (``causal_conv``, ``l2_norm``: looked up when the step is traced,
the same ones a test or a reference calls on whole arrays) and writes the
block of row ``b * H + h``. The block's place is the change of layout:
nothing is transposed in HBM, and no float32 array of ``[tokens, H * d]``
exists.

The backward is a kernel of its own (``jax.custom_vjp``): it reads the
product's block with a halo on both sides and the cotangent's with the
rows after it, makes the convolution again, pulls the cotangent back
through SiLU and the norm (autodiff of ``_finish``, inside the kernel),
and writes the product's cotangent ``[B, S, H * d]`` in place (the
mirrored convolution with the same taps) and the taps' gradient, summed
over a row's tiles in VMEM and over the sequences outside.

On a TPU the kernel is compiled by Mosaic; elsewhere it runs in Pallas'
interpret mode, the same body (``lax.platform_dependent``: chosen when the
program is lowered). Mosaic needs ``d`` a multiple of 128 (every published
delta-rule head is 128 wide); interpret mode takes any.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from neutronstarlite_tpu.nn import seq as nnseq

TILE = 1024  # positions a grid step takes
HALO = 16  # rows fetched beside a tile: one packed bfloat16 tile, at least taps - 1


def tile_of(positions: int, tile: int = TILE) -> int:
    """The largest multiple of ``HALO`` that divides ``positions`` and is at
    most ``tile``."""
    if positions % HALO:
        raise ValueError(f"the delta-rule mixer's operands are made {HALO} positions at a "
                         f"time: a sequence of {positions} is no multiple")
    return max(t for t in range(HALO, min(tile, positions) + 1, HALO) if positions % t == 0)


def _finish(y, scale, dtype):
    """From the convolution's float32 result to the operand: SiLU and,
    where ``scale`` is given, unit length over the last axis times it."""
    s = jax.nn.silu(y)
    if scale is not None:
        s = nnseq.l2_norm(s) * scale
    return s.astype(dtype)


def _held(ref, there):
    """The halo block ``ref``, zeros where it lies outside the sequence."""
    block = ref[...]
    return jnp.where(there, block, jnp.zeros_like(block))


def _forward_kernel(before_ref, x_ref, w_ref, o_ref, *, scale):
    rows = jnp.concatenate([_held(before_ref, pl.program_id(2) > 0), x_ref[...]], axis=1)
    y = nnseq.causal_conv(rows, w_ref[...])[:, HALO:]
    o_ref[...] = _finish(y, scale, o_ref.dtype)


def _backward_kernel(before_ref, x_ref, after_ref, w_ref, g_ref, g_after_ref, dx_ref, dw_ref,
                     *, scale, tiles):
    j = pl.program_id(2)
    tile = x_ref.shape[1]
    w = w_ref[...]
    taps = w.shape[-1]
    rows = jnp.concatenate([_held(before_ref, j > 0), x_ref[...],
                            _held(after_ref, j < tiles - 1)], axis=1)
    g = jnp.concatenate([g_ref[...], _held(g_after_ref, j < tiles - 1)], axis=1)
    # the tile's positions and the HALO after it, whose cotangents reach back into the tile
    y = nnseq.causal_conv(rows, w)[:, HALO:]
    _, pull = jax.vjp(lambda y: _finish(y, scale, g.dtype), y)
    (dy,) = pull(g)
    # the mirrored convolution: position t gathers tap i from position t + taps - 1 - i
    dx = sum(dy[:, taps - 1 - i: taps - 1 - i + tile] * w[:, i] for i in range(taps))
    dx_ref[...] = dx.astype(dx_ref.dtype)
    own = dy[:, :tile]
    read = rows[:, HALO - (taps - 1): HALO + tile].astype(jnp.float32)
    dw = jnp.stack([jnp.sum(own * read[:, i: i + tile], axis=(0, 1)) for i in range(taps)])

    @pl.when(j == 0)
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    dw_ref[...] += dw[None]


def _specs(s, h, d, taps, tile):
    """Block specs over the grid (sequence, head, tile): the product's
    column block, the HALO rows before and after it (clamped into the
    sequence: the kernel zeroes what the clamp brought), the head's taps,
    the operand's row."""
    per = tile // HALO
    last = s // HALO - 1
    return {
        "taps": pl.BlockSpec((d, taps), lambda b_, h_, j: (h_, 0)),
        "x": pl.BlockSpec((1, tile, d), lambda b_, h_, j: (b_, j, h_)),
        "before": pl.BlockSpec((1, HALO, d), lambda b_, h_, j: (b_, jnp.maximum(j * per - 1, 0), h_)),
        "after": pl.BlockSpec((1, HALO, d), lambda b_, h_, j: (b_, jnp.minimum((j + 1) * per, last), h_)),
        "row": pl.BlockSpec((1, tile, d), lambda b_, h_, j: (b_ * h + h_, j, 0)),
        "row_after": pl.BlockSpec(
            (1, HALO, d), lambda b_, h_, j: (b_ * h + h_, jnp.minimum((j + 1) * per, last), 0)),
    }


def _forward(x, taps, *, scale, heads, tile, interpret):
    b, s, wide = x.shape
    d = wide // heads
    sp = _specs(s, heads, d, taps.shape[1], tile)
    return pl.pallas_call(
        functools.partial(_forward_kernel, scale=scale),
        grid=(b, heads, s // tile),
        in_specs=[sp["before"], sp["x"], sp["taps"]],
        out_specs=sp["row"],
        out_shape=jax.ShapeDtypeStruct((b * heads, s, d), x.dtype),
        interpret=interpret, name="kda_conv_operand",
    )(x, x, taps)


def _backward(x, taps, g, *, scale, heads, tile, interpret):
    b, s, wide = x.shape
    d, k = wide // heads, taps.shape[1]
    sp = _specs(s, heads, d, k, tile)
    dx, dw = pl.pallas_call(
        functools.partial(_backward_kernel, scale=scale, tiles=s // tile),
        grid=(b, heads, s // tile),
        in_specs=[sp["before"], sp["x"], sp["after"], sp["taps"], sp["row"], sp["row_after"]],
        out_specs=[sp["x"], pl.BlockSpec((1, k, d), lambda b_, h_, j: (b_, 0, h_))],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((b, k, wide), jnp.float32)],
        interpret=interpret, name="kda_conv_operand_backward",
    )(x, x, x, taps, g, g)
    return dx, dw.sum(axis=0).T


def _on_platform(call, *args, **static):
    return lax.platform_dependent(
        *args, tpu=functools.partial(call, interpret=False, **static),
        default=functools.partial(call, interpret=True, **static))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def conv_operand(x, taps, scale, heads, tile=TILE):
    """One of q, k, v ``[B * heads, S, d]`` in ``x``'s dtype from the
    product ``x [B, S, heads * d]`` and the convolution's ``taps [heads * d,
    K]`` float32: row ``b * heads + h`` is ``causal_conv`` of sequence
    ``b``'s channels ``h d .. (h + 1) d``, then SiLU, then (``scale`` not
    None) ``l2_norm`` over those channels times ``scale``."""
    if taps.shape[1] - 1 > HALO:
        raise ValueError(f"a convolution of {taps.shape[1]} taps reads further back than the "
                         f"{HALO} rows fetched beside a tile")
    return _on_platform(_forward, x, taps, scale=scale, heads=heads, tile=tile_of(x.shape[1], tile))


def _conv_operand_fwd(x, taps, scale, heads, tile):
    return conv_operand(x, taps, scale, heads, tile), (x, taps)


def _conv_operand_bwd(scale, heads, tile, saved, g):
    x, taps = saved
    return _on_platform(_backward, x, taps, g, scale=scale, heads=heads,
                        tile=tile_of(x.shape[1], tile))


conv_operand.defvjp(_conv_operand_fwd, _conv_operand_bwd)
