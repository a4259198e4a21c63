"""What one optimizer step of the Qwen3-Next stack (gated delta-rule and
gated grouped-query attention mixers, every layer with experts) needs, from
shapes alone: the operations and bytes of the algorithm, not of any
implementation. ``shape`` is what ``inputs/token_qwen3_next.py`` gives.

Operations. A product of ``[m, a] x [a, b]`` is ``2 m a b``; trained it
costs three (forward, gradient of the weight, gradient of the input);
nothing is recomputed. Per token:

- a delta-rule layer's products: ``Wq``, ``Wk`` (hidden x Hk d), ``Wv``,
  ``Wz`` (hidden x Hv d), ``Wo`` (Hv d x hidden), ``Wa``, ``Wb`` (hidden x
  Hv each), and the convolutions (``K`` multiply-adds a channel of q, k, v);
- a delta-rule layer's **recurrence**, the position-by-position form (no
  chunked form's extra products are priced): per **value** head the
  state's decay (``dk dv``), ``S'^T k`` (``2 dk dv``), the rank-one update
  (``dk dv``) and ``S^T q`` (``2 dk dv``): ``6 dk dv``;
- an attention layer's projections (``Wq`` with its gate hidden x H 2 D,
  ``Wk``, ``Wv`` hidden x Hkv D, ``Wo`` H D x hidden) and, **the causal
  half only**, its pairs: ``2 D + 2 D`` per pair and **query** head;
- the shared expert's SwiGLU and its gate, the router, the routed experts
  **per pair routed to a held expert** (``routed_rows``), the head over the
  vocabulary slice held here. There is no dense layer.

Bytes: Adam reads weight, gradient, m, v and writes weight, m, v (28 bytes
a parameter); a product reads its weight once forward and once backward in
the compute dtype and writes the gradient in float32; the residual stream
once per layer and direction; the attention's and the recurrence's own
operands once a direction: the attention reads K and V **once a group**
(``Hkv`` heads, not ``H``), the recurrence reads q and k once a key head
and the log-decay as 4 bytes a value head. Beside the operations these are
small: they are counted so that the roofline names its bound.
"""

from __future__ import annotations

from typing import Dict

from harness import spec

# the grouped SwiGLU over the routed rows is the DeepSeek-V3 block's, counted once
_block = spec.named_module("needs", "moonlight")
ADAM_BYTES_PER_PARAMETER = _block.ADAM_BYTES_PER_PARAMETER
experts_need = _block.experts_need


def attending_layers(shape: dict) -> float:
    """Layers of a step whose mixer is the attention, **read from the
    cell's shape** (the program's ``gqa.token_layers`` over its tokens),
    not assumed from the depth."""
    return shape["gqa_token_layers"] / shape["tokens"]


def attention_need(shape: dict, trained: bool = True) -> Dict[str, float]:
    """The streamed causal attention alone (the ``seq/gqa/attend`` scope),
    ONE layer: scores and aggregates over the causal pairs, ``2 D + 2 D``
    operations a pair and query head; it reads q and writes out once a
    query head, reads k and v once a key/value head, per direction (and
    their gradients back)."""
    s, dim = shape["length"], shape["nope"] + shape["rope"]
    pairs = shape["sequences"] * s * (s + 1) / 2.0
    flops = pairs * shape["heads"] * (2.0 * dim + 2.0 * shape["v_head"])
    io = shape["tokens"] * (shape["heads"] * (dim + shape["v_head"])
                            + shape["kv_heads"] * (dim + shape["v_head"])) * shape["itemsize"]
    return {"flops": flops * (3.0 if trained else 1.0), "bytes": io * (3.0 if trained else 1.0)}


def recurrence_need(shape: dict, trained: bool = True) -> Dict[str, float]:
    """The delta rule alone (the ``seq/kda/recur`` scope), all delta-rule
    layers of a step, whatever implements it: ``6 dk dv`` operations a
    token, **value** head and layer; it reads v and writes o once a value
    head, reads q and k once a key head (compute dtype), the log-decay (4
    bytes a value head) and beta, once a direction (their gradients
    back)."""
    d, rows = shape["kda_dim"], shape["kda_token_layers"]
    value, key = shape["kda_value_heads"], shape["kda_key_heads"]
    flops = rows * value * 6.0 * d * d
    io = rows * ((2 * value + 2 * key) * d * shape["itemsize"] + value * (4 + 4))
    return {"flops": flops * (3.0 if trained else 1.0), "bytes": io * (3.0 if trained else 1.0)}


def forward_flops_per_token_parts(shape: dict) -> Dict[str, float]:
    """Forward operations a token, by part."""
    d, h, dim = shape["hidden"], shape["heads"], shape["nope"] + shape["rope"]
    key_wide = shape["kda_key_heads"] * shape["kda_dim"]
    value_wide = shape["kda_value_heads"] * shape["kda_dim"]
    delta = 2.0 * (d * (2 * key_wide + 3 * value_wide) + 2 * d * shape["kda_value_heads"]
                   + shape["conv_kernel"] * (2 * key_wide + value_wide))
    attending = 2.0 * (d * h * 2 * dim + 2 * d * shape["kv_heads"] * dim + h * dim * d)
    return {
        "delta_products": shape["kda_layers"] * delta,
        "delta_recurrence": recurrence_need(shape, trained=False)["flops"] / shape["tokens"],
        "attention_projections": attending_layers(shape) * attending,
        "attention_pairs": (attending_layers(shape) * attention_need(shape, trained=False)["flops"]
                            / shape["tokens"]),
        "shared_experts": shape["moe_layers"] * 2.0 * d * (3 * shape["shared_width"] + 1),
        "router": shape["moe_layers"] * 2.0 * d * shape["routed"],
        "routed_experts": experts_need(shape, trained=False)["flops"] / shape["tokens"],
        "head": 2.0 * d * shape["vocab"],
    }


def epoch_need(shape: dict) -> Dict[str, float]:
    """Operations and bytes of one optimizer step over one batch (an epoch
    of this family): least operations, causal half only, the recurrence as
    written, no recomputation."""
    flops = 3.0 * sum(forward_flops_per_token_parts(shape).values()) * shape["tokens"]
    stream = 2 * 2 * shape["moe_layers"] * shape["tokens"] * shape["hidden"] * 4
    weights = shape["parameters"] * (2 * shape["itemsize"] + 4)
    return {"flops": flops, "bytes": float(
        shape["parameters"] * ADAM_BYTES_PER_PARAMETER + weights + stream
        + attending_layers(shape) * attention_need(shape)["bytes"] + recurrence_need(shape)["bytes"])}
