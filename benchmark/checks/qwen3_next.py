"""Check of the Qwen3-Next token-sequence cell (the SEQLM trainer over
gated delta-rule layers and a gated grouped-query attention layer, every
layer with experts) against ``reference/qwen3_next.py``: float32 at the
highest matmul precision, the recurrence position by position, K and V
repeated per query head, softmax over all experts then the top 10, told the
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
kept layers, one of each mixer: the third delta-rule layer (every leaf of
the mixer but ``a_log`` and ``dt_bias``, 32 entries each, whose first Adam
steps are one float32 grain and read 0.35-0.55 by the norm when ONE entry's
sign differs (``reference/qwen3_next.py:tail_of``): the three projections
and their convolutions, the decay's and the write strength's products, the
output gate's product and norm, ``wo``; the two norms, the router, the shared expert and
its gate; NOT its routed experts' three matrices, 100.7M of the layer's
138.6M entries: the check keeps several copies of the tail on the host,
some in float64, and with them it met the one-chip machine's 40 GiB in
every run, my chip runs, PR 35) and the attention layer after it (of its
32 held experts the first 8 experts' matrices, for the same reason; the
query
projection with its gate, the key and value projections, the two head
norms, ``wo``, the two norms, its experts), the final norm and the head.
The reference differentiates the attention layer in blocks of 256 queries
(its ``[16, 8192, 8192]`` float32 probabilities would be 4.3 GB a
sequence), the gradient of its keys and values summed over the blocks, and
hands the gradient of its input down to the delta-rule layer, which it
differentiates a whole sequence at a time.

**Not compared on the chip**: the gradients of the third delta-rule layer's
routed experts and of the attention layer's held experts 8 to 31 (above),
of the embedding and of the first two delta-rule layers (the reference would have to carry the
stream's gradient down through two more whole-sequence backward passes).
Their forward is in ``logits_rel`` and ``loss_rel``; they run the scanned
code of the delta-rule layer that is compared; every leaf of every layer
is held to the reference by ``tests/test_gdn.py`` at a small size, and the
chunked per-head delta rule and the grouped attention to their plain forms,
outputs and gradients, by the same file.
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
    std 0.02, norms at zero and the delta rule's output norm at one, the
    convolutions, ``a_log`` and ``dt_bias`` drawn as the configuration
    assumes them): no trainer is built."""
    rng = np.random.default_rng(seed)
    d, h, kv = int(model["hidden_size"]), int(model["num_attention_heads"]), int(model["num_key_value_heads"])
    head, width = int(model["head_dim"]), int(model["moe_intermediate_size"])
    routed, shared = int(model["num_experts"]), int(model["shared_expert_intermediate_size"])
    kh, vh = int(model["linear_num_key_heads"]), int(model["linear_num_value_heads"])
    kd, taps = int(model["linear_key_head_dim"]), int(model["linear_conv_kernel_dim"])
    every = int(model["full_attention_interval"])

    def normal(*shape):
        return rng.standard_normal(shape, dtype=np.float32) * np.float32(0.02)

    def uniform(lo, hi, *shape):
        return rng.uniform(lo, hi, shape).astype(np.float32)

    def zeros(*shape):
        return np.zeros(shape, np.float32)

    def attending(n):
        return {
            "norm1": zeros(n, d), "wq": normal(n, d, h * 2 * head), "wk": normal(n, d, kv * head),
            "wv": normal(n, d, kv * head), "q_norm": zeros(n, head), "k_norm": zeros(n, head),
            "wo": normal(n, h * head, d), "norm2": zeros(n, d),
        }

    def delta(n):
        step = np.exp(uniform(np.log(1e-3), np.log(1e-1), n, vh))
        return {
            "norm1": zeros(n, d),
            "wq": normal(n, d, kh * kd), "wk": normal(n, d, kh * kd), "wv": normal(n, d, vh * kd),
            **{c: uniform(-taps ** -0.5, taps ** -0.5, n, wide, taps)
               for c, wide in (("cq", kh * kd), ("ck", kh * kd), ("cv", vh * kd))},
            "wf": normal(n, d, vh), "a_log": np.log(uniform(1.0, 16.0, n, vh)),
            "dt_bias": (step + np.log(-np.expm1(-step))).astype(np.float32),
            "wb": normal(n, d, vh), "wz": normal(n, d, vh * kd),
            "o_norm": np.ones((n, kd), np.float32), "wo": normal(n, vh * kd, d), "norm2": zeros(n, d),
        }

    runs = []  # [attends, layers] in the stack's order
    for i in range(layers):
        attends = (i + 1) % every == 0
        if runs and runs[-1][0] == attends:
            runs[-1][1] += 1
        else:
            runs.append([attends, 1])
    params = {"embed": normal(vocab, d)}
    for i, (attends, n) in enumerate(runs):
        params[f"moe{i or ''}"] = {
            **(attending(n) if attends else delta(n)), "router": normal(n, d, routed),
            "eg": normal(n, held, d, width), "eu": normal(n, held, d, width), "ed": normal(n, held, width, d),
            "sg": normal(n, d, shared), "su": normal(n, d, shared), "sd": normal(n, shared, d),
            "sgate": normal(n, d, 1),
        }
    params["norm"], params["head"] = zeros(d), normal(d, vocab)
    return params


def control(ctx) -> Dict[str, float]:
    """The errors ``check`` would return if the program were the plain
    reference computed in the nearest precision below the one the
    configuration states (fp8 operands for bfloat16; the recurrence's and
    the attention's included): by the measures and at the size of the check
    itself, at the control's own seeded weights, on one batch: its logits
    and choices against the float32 reference following them, and its first
    step (loss, gradients of the tail, Adam's update from them). They have
    to fail the configuration's limits (benchmark/control.py)."""
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
