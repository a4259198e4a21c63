"""What one optimizer step of the DeepSeek-V3 block needs, from shapes
alone: the operations and bytes of the algorithm, not of any
implementation. ``shape`` is what ``inputs/token_corpus.py`` gives.

Operations. A product of ``[m, a] x [a, b]`` is ``2 m a b``. Trained, a
product costs three (forward, gradient of the weight, gradient of the
input); nothing is recomputed. Per token and layer:

- latent attention's projections: ``Wq`` (hidden x heads (nope + rope)),
  ``Wkv_a`` (hidden x (kv_rank + rope)), ``Wkv_b`` (kv_rank x heads (nope +
  v_head)), ``Wo`` (heads v_head x hidden);
- attention's pairs, **the causal half only**: a sequence of S positions
  has S (S + 1) / 2 pairs (i, j <= i); each costs ``2 (nope + rope)`` for
  its score and ``2 v_head`` for its share of the aggregate, per head.
  Trained, the pairs cost three times their forward like any product:
  the backward makes four products per pair (dV and dP at v_head, dQ and
  dK at nope + rope) where the forward makes two. It also needs the
  scores again, which a streamed attention recomputes and this count does
  not price;
- the dense layer's SwiGLU ``3 x hidden x ffn``; the shared experts' SwiGLU
  ``3 x hidden x shared_width``; the router ``hidden x routed``;
- routed experts: ``3 x hidden x expert_width`` per **pair routed to a held
  expert** (``routed_rows``, the window's ``moe.rows_routed`` an epoch),
  not per pair the batch could route;
- the head ``hidden x vocab`` (the slice held here).

Bytes: Adam reads the float32 weight, gradient, m and v and writes the
weight, m and v (28 bytes a parameter); a product reads its weight once
forward and once backward in the compute dtype and writes the gradient
in float32; the residual stream is read and written once per layer and
direction. Beside the operations these are small (the step is bound by
the matrix units): they are counted so that the roofline names its bound.
"""

from __future__ import annotations

from typing import Dict

ADAM_BYTES_PER_PARAMETER = 28  # reads w, g, m, v; writes w, m, v; float32


def attention_pairs(shape: dict) -> float:
    """Causal pairs of a step: sequences x S (S + 1) / 2."""
    s = shape["length"]
    return shape["sequences"] * s * (s + 1) / 2.0


def attention_need(shape: dict, trained: bool = True) -> Dict[str, float]:
    """The streamed causal attention alone (the ``seq/mla/attend`` scope):
    scores and aggregates over the causal pairs, per head; it reads q, k, v
    and writes out once per direction (and their gradients back)."""
    per_pair = 2.0 * (shape["nope"] + shape["rope"]) + 2.0 * shape["v_head"]
    flops = attention_pairs(shape) * shape["heads"] * per_pair
    rows = shape["tokens"] * shape["heads"]
    io = rows * (2 * (shape["nope"] + shape["rope"]) + 2 * shape["v_head"]) * shape["itemsize"]
    if trained:
        return {"flops": 3.0 * flops, "bytes": 3.0 * io}
    return {"flops": flops, "bytes": float(io)}


def experts_need(shape: dict, trained: bool = True) -> Dict[str, float]:
    """The grouped SwiGLU over the rows routed to held experts (the
    ``seq/moe/experts`` scope): three products per row; every held expert's
    three matrices are read once per direction, the rows in and out."""
    rows = shape["routed_rows"]
    flops = rows * 3 * 2.0 * shape["hidden"] * shape["expert_width"]
    weights = shape["moe_layers"] * shape["held"] * 3 * shape["hidden"] * shape["expert_width"]
    io = (weights + rows * (2 * shape["hidden"] + 2 * shape["expert_width"])) * shape["itemsize"]
    return {"flops": flops * (3.0 if trained else 1.0), "bytes": io * (3.0 if trained else 1.0)}


def forward_flops_per_token_parts(shape: dict) -> Dict[str, float]:
    """Forward operations a token, by part (whole model held here)."""
    d, h = shape["hidden"], shape["heads"]
    layers = shape["moe_layers"] + 1
    project = 2.0 * (
        d * h * (shape["nope"] + shape["rope"]) + d * (shape["kv_rank"] + shape["rope"])
        + shape["kv_rank"] * h * (shape["nope"] + shape["v_head"]) + h * shape["v_head"] * d
    )
    return {
        "projections": layers * project,
        "attention_pairs": layers * attention_need(shape, trained=False)["flops"] / shape["tokens"],
        "dense_mlp": 3 * 2.0 * d * shape["ffn"],
        "shared_experts": shape["moe_layers"] * 3 * 2.0 * d * shape["shared_width"],
        "router": shape["moe_layers"] * 2.0 * d * shape["routed"],
        "routed_experts": experts_need(shape, trained=False)["flops"] / shape["tokens"],
        "head": 2.0 * d * shape["vocab"],
    }


def epoch_need(shape: dict) -> Dict[str, float]:
    """Operations and bytes of one optimizer step over one batch (an epoch
    of this family): least operations, causal half only, no
    recomputation."""
    parts = forward_flops_per_token_parts(shape)
    layers = shape["moe_layers"] + 1
    products = sum(v for k, v in parts.items() if k != "attention_pairs")
    pairs = layers * attention_need(shape, trained=True)["flops"]
    flops = 3.0 * products * shape["tokens"] + pairs
    stream = 2 * 2 * layers * shape["tokens"] * shape["hidden"] * 4
    weights = shape["parameters"] * (2 * shape["itemsize"] + 4)
    return {"flops": flops, "bytes": float(
        shape["parameters"] * ADAM_BYTES_PER_PARAMETER + weights + stream
        + layers * attention_need(shape)["bytes"])}
