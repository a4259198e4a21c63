"""What one optimizer step of the hybrid stack (KDA and latent-attention
mixers, a dense MLP then expert layers) needs, from shapes alone: the
operations and bytes of the algorithm, not of any implementation. ``shape``
is what ``inputs/token_hybrid.py`` gives.

Operations. A product of ``[m, a] x [a, b]`` is ``2 m a b``; trained it
costs three (forward, gradient of the weight, gradient of the input);
nothing is recomputed. Per token:

- a KDA layer's products: ``Wq``, ``Wk``, ``Wv`` (hidden x H d), ``Wo``
  (H d x hidden), the decay's and the output gate's rank-``d`` pairs
  (hidden x d, d x H d), ``Wb`` (hidden x H), and the convolutions
  (``K`` multiply-adds a channel, three times);
- a KDA layer's **recurrence**, the position-by-position form (no chunked
  form's extra products are priced): per head the state's decay (``dk
  dv``), ``S'^T k`` (``2 dk dv``), the rank-one update (``dk dv``) and
  ``S^T q`` (``2 dk dv``): ``6 dk dv``;
- a latent-attention layer's projections and, **the causal half only**,
  its pairs: ``2 (nope + shared) + 2 v_head`` per pair and head;
- the dense SwiGLU, the shared expert's SwiGLU, the router, the routed
  experts **per pair routed to a held expert** (``routed_rows``), the head
  over the vocabulary slice held here.

Bytes: Adam reads weight, gradient, m, v and writes weight, m, v (28 bytes
a parameter); a product reads its weight once forward and once backward in
the compute dtype and writes the gradient in float32; the residual stream
once per layer and direction; the attention's and the recurrence's own
operands once a direction. Beside the operations these are small: they are
counted so that the roofline names its bound.
"""

from __future__ import annotations

from typing import Dict

from harness import spec

# the parts this stack shares with the DeepSeek-V3 block, counted once: one
# latent-attention layer's causal pairs (its ``rope`` dims are in the score
# whether or not they are turned) and the grouped SwiGLU over the routed rows
_block = spec.named_module("needs", "moonlight")
ADAM_BYTES_PER_PARAMETER = _block.ADAM_BYTES_PER_PARAMETER
attention_need = _block.attention_need  # ONE layer; ``seq/mla/attend`` holds ``mla_layers``
experts_need = _block.experts_need


def recurrence_need(shape: dict, trained: bool = True) -> Dict[str, float]:
    """The delta rule alone (the ``seq/kda/recur`` scope), all KDA layers
    of a step, whatever implements it: ``6 dk dv`` operations a token, head
    and layer; it reads q, k, v (compute dtype), the log-decay (float32 a
    key channel) and beta, and writes o, once a direction (their gradients
    back)."""
    d, rows = shape["kda_dim"], shape["kda_token_layers"] * shape["kda_heads"]
    flops = rows * 6.0 * d * d
    io = rows * (4 * d * shape["itemsize"] + d * 4 + 4)
    return {"flops": flops * (3.0 if trained else 1.0), "bytes": io * (3.0 if trained else 1.0)}


def forward_flops_per_token_parts(shape: dict) -> Dict[str, float]:
    """Forward operations a token, by part."""
    d, h = shape["hidden"], shape["heads"]
    kd, wide = shape["kda_dim"], shape["kda_heads"] * shape["kda_dim"]
    kda = 2.0 * (4 * d * wide + 2 * (d * kd + kd * wide) + d * shape["kda_heads"]
                 + 3 * shape["conv_kernel"] * wide)
    latent = 2.0 * (
        d * h * (shape["nope"] + shape["rope"]) + d * (shape["kv_rank"] + shape["rope"])
        + shape["kv_rank"] * h * (shape["nope"] + shape["v_head"]) + h * shape["v_head"] * d
    )
    return {
        "kda_products": shape["kda_layers"] * kda,
        "kda_recurrence": recurrence_need(shape, trained=False)["flops"] / shape["tokens"],
        "latent_projections": shape["mla_layers"] * latent,
        "attention_pairs": (shape["mla_layers"] * attention_need(shape, trained=False)["flops"]
                            / shape["tokens"]),
        "dense_mlp": 3 * 2.0 * d * shape["ffn"],
        "shared_experts": shape["moe_layers"] * 3 * 2.0 * d * shape["shared_width"],
        "router": shape["moe_layers"] * 2.0 * d * shape["routed"],
        "routed_experts": experts_need(shape, trained=False)["flops"] / shape["tokens"],
        "head": 2.0 * d * shape["vocab"],
    }


def epoch_need(shape: dict) -> Dict[str, float]:
    """Operations and bytes of one optimizer step over one batch (an epoch
    of this family): least operations, causal half only, the recurrence as
    written, no recomputation."""
    flops = 3.0 * sum(forward_flops_per_token_parts(shape).values()) * shape["tokens"]
    layers = shape["moe_layers"] + 1
    stream = 2 * 2 * layers * shape["tokens"] * shape["hidden"] * 4
    weights = shape["parameters"] * (2 * shape["itemsize"] + 4)
    return {"flops": flops, "bytes": float(
        shape["parameters"] * ADAM_BYTES_PER_PARAMETER + weights + stream
        + shape["mla_layers"] * attention_need(shape)["bytes"] + recurrence_need(shape)["bytes"])}
