"""Check of the hybrid token-sequence cell (the SEQLM trainer over KDA and
latent-attention layers) against ``reference/kimi_linear.py``: float32 at
the highest matmul precision, the recurrence position by position, told the
same share (experts held, vocabulary slice), at the published widths and
the timed sizes. After the window and the memory reading.

**What is compared is what ``checks/moonlight.py`` compares, by its own
``check``** (loaded from that file: the reference is the one this
configuration names, and offers the same calls), under the same names:
``logits_rel`` and ``route_mismatch`` of the eval forward at the warm-up's
weights on the window's last batch at a seeded sample of positions, the
reference following the program's choice of experts; then the first two
steps replayed from the seed's initial state with the program's own
compiled step: ``loss_rel`` (each recorded loss against the reference's at
the same weights; a replayed loss that is not the recorded one bit for bit
is a fault), ``grads_rel`` (the step's gradients read back from Adam's
first moment, worst leaf by the norm) and ``update_rel`` (the weights'
change against the reference's Adam).

Gradients and updates are compared for ``reference.tail_of``: the last two
kept layers, one of each mixer: the latent-attention expert layer without
positions (its factors, norms, router, routed and shared experts) and the
KDA expert layer after it (every KDA leaf: the three projections and their
convolutions, the decay's pair, ``a_log``, ``dt_bias``, ``wb``, the output
gate's pair and norm, ``wo``; the two norms, the router, the routed and the
shared experts), the final norm and the head. The reference differentiates
the KDA layer a whole sequence at a time (position by position its memory
is linear in the positions) and the latent-attention layer in blocks of
256 queries (its ``[32, 8192, 8192]`` float32 probabilities would be 8.6 GB
a sequence), the gradient of its keys and values summed over the blocks.

**Not compared on the chip**: the gradients of the embedding, of the first
layer (KDA + dense MLP) and of the two KDA expert layers before the
latent-attention layer: the reference would have to carry the stream's
gradient down through the latent attention's blocks as well. Their forward
is in ``logits_rel`` and ``loss_rel``; they run the code of the KDA layer
that is compared; the dense layer's and the embedding's backward, and every
other leaf, are held to the reference by
``tests/test_seqlm.py::test_the_hybrid_trainer_matches_the_reference`` at a
small size, and the chunked delta rule to the recurrence, outputs and
gradients, by ``tests/test_kda.py``.
"""

from __future__ import annotations

from typing import Dict

import numpy as np

from harness import correct, spec

_shared = spec.named_module("checks", "moonlight")
check = _shared.check
trainer_family = _shared.trainer_family

CONTROL_DTYPE = {"bfloat16": "float8_e4m3fn", "float32": "bfloat16"}


def control_params(model: dict, layers: int, held: int, vocab: int, seed: int):
    """The control's own seeded weights in the program's layout (normal,
    std 0.02, norms at one, the KDA's convolutions, ``a_log`` and
    ``dt_bias`` drawn as the configuration assumes them): no trainer is
    built."""
    rng = np.random.default_rng(seed)
    d, h = int(model["hidden_size"]), int(model["num_attention_heads"])
    nope, shared_dims = int(model["qk_nope_head_dim"]), int(model["qk_rope_head_dim"])
    rank, v_head = int(model["kv_lora_rank"]), int(model["v_head_dim"])
    width, routed = int(model["moe_intermediate_size"]), int(model["num_experts"])
    linear = model["linear_attn_config"]
    kh, kd, taps = int(linear["num_heads"]), int(linear["head_dim"]), int(linear["short_conv_kernel_size"])

    def normal(*shape):
        return rng.standard_normal(shape, dtype=np.float32) * np.float32(0.02)

    def uniform(lo, hi, *shape):
        return rng.uniform(lo, hi, shape).astype(np.float32)

    def latent(*lead):
        return {
            "norm1": np.ones(lead + (d,), np.float32), "wq": normal(*lead, d, h * (nope + shared_dims)),
            "wkv_a": normal(*lead, d, rank + shared_dims), "kv_norm": np.ones(lead + (rank,), np.float32),
            "wkv_b": normal(*lead, rank, h * (nope + v_head)), "wo": normal(*lead, h * v_head, d),
            "norm2": np.ones(lead + (d,), np.float32),
        }

    def kda(*lead):
        step = np.exp(uniform(np.log(1e-3), np.log(1e-1), *lead, kh * kd))
        return {
            "norm1": np.ones(lead + (d,), np.float32),
            "wq": normal(*lead, d, kh * kd), "wk": normal(*lead, d, kh * kd), "wv": normal(*lead, d, kh * kd),
            **{c: uniform(-taps ** -0.5, taps ** -0.5, *lead, kh * kd, taps) for c in ("cq", "ck", "cv")},
            "wf_a": normal(*lead, d, kd), "wf_b": normal(*lead, kd, kh * kd),
            "a_log": np.log(uniform(1.0, 16.0, *lead, kh)),
            "dt_bias": (step + np.log(-np.expm1(-step))).astype(np.float32),
            "wb": normal(*lead, d, kh), "wz_a": normal(*lead, d, kd), "wz_b": normal(*lead, kd, kh * kd),
            "o_norm": np.ones(lead + (kd,), np.float32), "wo": normal(*lead, kh * kd, d),
            "norm2": np.ones(lead + (d,), np.float32),
        }

    mixer = {True: kda, False: latent}
    is_kda = [i + 1 in linear["kda_layers"] for i in range(layers)]
    ffn, shared = int(model["intermediate_size"]), int(model["num_shared_experts"]) * width
    params = {
        "embed": normal(vocab, d),
        "dense": {**mixer[is_kda[0]](), "wg": normal(d, ffn), "wu": normal(d, ffn), "wd": normal(ffn, d)},
    }
    runs = []  # [kind, layers] of the expert layers, in the stack's order
    for kind in is_kda[1:]:
        if runs and runs[-1][0] == kind:
            runs[-1][1] += 1
        else:
            runs.append([kind, 1])
    for i, (kind, n) in enumerate(runs):
        params[f"moe{i or ''}"] = {
            **mixer[kind](n), "router": normal(n, d, routed),
            "eg": normal(n, held, d, width), "eu": normal(n, held, d, width), "ed": normal(n, held, width, d),
            "sg": normal(n, d, shared), "su": normal(n, d, shared), "sd": normal(n, shared, d),
        }
    params["norm"], params["head"] = np.ones((d,), np.float32), normal(d, vocab)
    return params


def control(ctx) -> Dict[str, float]:
    """The errors ``check`` would return if the program were the plain
    reference computed in the nearest precision below the one the
    configuration states (fp8 operands for bfloat16; the recurrence's
    included): by the measures and at the size of the check itself, at the
    control's own seeded weights, on one batch: its logits and choices
    against the float32 reference following them, and its first step (loss,
    gradients of the tail, Adam's update from them). They have to fail the
    configuration's limits (benchmark/control.py)."""
    import jax.numpy as jnp

    config = ctx.config
    inputs_of = spec.config_module(config, "inputs")
    ref = correct.reference_module(config)
    tree, host = _shared._tree, _shared._host
    model = inputs_of.program_model(config, ctx.rehearse)
    cfg = dict(config["cfg"], **(config["rehearse"].get("cfg", {}) if ctx.rehearse else {}))
    held = int(model["num_experts"]) // int(cfg["EXPERT_SHARDS"])
    vocab = int(model["vocab_size"]) // int(cfg["VOCAB_SHARDS"])
    length, sequences = int(cfg["SEQ_LENGTH"]), int(cfg["SEQ_BATCH"])
    learn_rate, weight_decay = float(cfg["LEARN_RATE"]), float(cfg["WEIGHT_DECAY"])
    warmup = int(cfg.get("WARMUP_EPOCHS", 0))
    shape, share = ref.Shape.of(model), ref.Share(int(cfg["EXPERT_SHARD"]) * held, held)
    block = min(_shared.BLOCK, length)
    dtype = getattr(jnp, CONTROL_DTYPE[str(cfg.get("PRECISION", "float32"))])

    batch = inputs_of.make_tokens(sequences, length, vocab, ctx.seed)
    params = tree(jnp.asarray, control_params(model, int(cfg["SEQ_LAYERS"]), held, vocab, ctx.seed))
    rows = _shared.sample_rows(ctx.seed, sequences * length)
    low_logits, low_choice = _shared.reference_at_rows(
        ref, params, batch, rows, None, shape, share, None, block, dtype)
    ref_logits, own = _shared.reference_at_rows(
        ref, params, batch, rows, low_choice, shape, share, None, block)
    errors = {
        "logits_rel": correct.relative_error(low_logits, ref_logits),
        "route_mismatch": _shared.mismatched(low_choice, own) / float(np.prod(own.shape[:-1])),
    }
    low_loss, low_grads = ref.tail_loss_and_grads(params, batch, shape, share, None, low_choice,
                                                  block, dtype)
    ref_loss, ref_grads = ref.tail_loss_and_grads(params, batch, shape, share, None, low_choice, block)
    low_grads, ref_grads = host(low_grads), host(ref_grads)
    before = host(ref.tail_of(params))
    zeros = tree(np.zeros_like, before)
    low_after, _, _ = _shared.adam_tree(
        ref, before, low_grads, zeros, zeros, 1, learn_rate, weight_decay, warmup)
    ref_after, _, _ = _shared.adam_tree(
        ref, before, ref_grads, zeros, zeros, 1, learn_rate, weight_decay, warmup)
    errors.update(
        loss_rel=abs(float(low_loss) - float(ref_loss)) / abs(float(ref_loss)),
        grads_rel=correct.gradient_error(low_grads, ref_grads),
        update_rel=correct.gradient_error(tree(lambda a, b: a - b, low_after, before),
                                          tree(lambda a, b: a - b, ref_after, before)),
    )
    return errors
