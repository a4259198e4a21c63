"""Check of a token-sequence cell (the SEQLM trainer over the DeepSeek-V3
block) against ``reference/moonlight.py``: float32 at the highest matmul
precision, told the same share (experts held, vocabulary slice), at the
published widths and the timed sizes, in blocks of queries so that it fits
beside the trainer's state. After the window and the memory reading.

What is compared, each under the name of its limit in the configuration's
``tolerance``:

- ``logits_rel``: the program's eval forward (its own jitted forward, the
  layers the step runs, no optimizer) at the weights the warm-up ended on,
  on the window's last batch, at a seeded sample of positions, against the
  reference *following the program's choice of experts*: largest
  difference relative to the largest reference logit.
- ``route_mismatch``: in that same pass, the share of (token, layer) pairs
  whose chosen experts differ from the reference's own choice at the same
  stream. A near-tie that rounding decides is no fault, and a handful in a
  hundred are expected; a wrong router (a bias added to the weights, a
  sigmoid left out, a top-k over the held experts only) moves most of them.
- **the first two steps, followed.** There is no dropout here, so the
  program's first steps are a function of the seed and can be replayed:
  the check builds the seed's initial state again (``initial_state``),
  and for each of the first two steps (a) keeps the weights before the
  step, (b) runs the program's own compiled step on the batch the run loop
  gave it, which hands out the choice of experts it made (the eval
  forward's choice differs from the step's at a few near-ties in a
  thousand, enough to read as 5% to 8% on the experts' gradients: my chip
  runs, PR 28), (c) has the reference compute the loss and the gradients
  at the kept weights, following the step's choice, and (d) has the
  reference make Adam's update from its own gradients.
  ``loss_rel``: each step's loss as the run recorded it
  (``loss_history``) against the reference's. A replayed loss that is not
  the recorded one bit for bit is a fault by name. ``grads_rel``: the
  program's gradients, read back exactly from the step's own first moment
  (``g = (m' - beta1 m) / (1 - beta1) - decay * w``: what the timed path
  computed, not a second program), against the reference's, by the norm,
  worst leaf. ``update_rel``: the change of the weights through the step
  against the reference's Adam on its own gradients and moments, by the
  norm, worst leaf.

Gradients and updates are compared for the last expert layer (its latent
attention factors, norms, router, routed and shared experts), the final
norm and the head: ``reference.tail_of``. **Not compared on the chip**: the
embedding, the dense layer and the expert layers before the last. Their
reference gradients need every layer's attention probabilities held at
once in float32 (4.3 GB a layer and sequence), which does not fit beside
the trainer's 9.1 GB; the expert layers before the last run the same
scanned code as the last; the dense layer's and the embedding's backward
are held to the reference by tests/test_seqlm.py at a small size.

Faults, exact: a weight the window left as the warm-up had it; a replayed
step whose loss is not the recorded one; (by the kind) a loss that is not
finite.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

from harness import correct, program, runtime

SAMPLE_POSITIONS = 256
FOLLOWED_STEPS = 2
BLOCK = 1024  # queries a block of the reference
BETA1, BETA2, EPSILON = 0.9, 0.999, 1e-9  # assumed: nn/param.py's Adam


def trainer_family(trainer) -> str:
    return "seqlm"


def _host(tree):
    import jax

    return jax.tree.map(lambda a: np.asarray(a, dtype=np.float32), tree)


def _choice_by_sequence(choice, sequences: int):
    """[L, sequences * S, k] -> [sequences, L, S, k]."""
    c = np.asarray(choice)
    return c.reshape(c.shape[0], sequences, -1, c.shape[-1]).transpose(1, 0, 2, 3)


def mismatched(a, b) -> int:
    """How many (token, layer) pairs chose different sets of experts."""
    return int(np.sum(np.any(np.sort(np.asarray(a), axis=-1) != np.sort(np.asarray(b), axis=-1), axis=-1)))


def sample_rows(seed: int, total: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return np.sort(rng.choice(total, size=min(SAMPLE_POSITIONS, total), replace=False))


def reference_at_rows(ref, params, batch, rows, choice, shape, share, bias, block, dtype=None):
    """(reference logits at the flat positions ``rows`` of ``batch``, the
    reference's own choices [sequences, L, S, k]); it follows ``choice``
    where one is given."""
    length = batch.shape[1]
    logits, own = [], []
    for s in range(batch.shape[0]):
        x, ch = ref.hidden_states(params, batch[s], shape, share, bias,
                                  None if choice is None else choice[s], block, dtype)
        own.append(np.asarray(ch))
        pos = rows[rows // length == s] % length
        if len(pos):
            logits.append(np.asarray(ref.head_logits(params, x[np.asarray(pos)], shape, dtype)))
    return np.concatenate(logits), np.stack(own)


def adam_tree(ref, p, g, m, v, step: int, learn_rate: float, weight_decay: float, warmup: int):
    """The reference's Adam over a tree: (p', m', v')."""
    import jax

    leaves, treedef = jax.tree.flatten(p)
    out = [ref.adam_step(a, b, c, d, step, learn_rate, weight_decay, BETA1, BETA2, EPSILON, warmup)
           for a, b, c, d in zip(leaves, treedef.flatten_up_to(g), treedef.flatten_up_to(m),
                                 treedef.flatten_up_to(v))]
    after, m, v = (treedef.unflatten([o[i] for o in out]) for i in range(3))
    # the weights are float32 masters: during the warm-up a step is a few
    # ulps of a weight of one (a norm), and the grain is part of the result
    return _tree(lambda a: a.astype(np.float32).astype(np.float64), after), m, v


def _worst_leaves(got, want, n: int = 4) -> str:
    """The ``n`` leaves with the largest ``norm_error``, for the log."""
    import jax

    errors = jax.tree_util.tree_leaves_with_path(jax.tree.map(correct.norm_error, got, want))
    ranked = sorted(errors, key=lambda kv: -kv[1])[:n]
    return ", ".join(f"{jax.tree_util.keystr(path)} {e:.4f}" for path, e in ranked)


def _tree(fn, *trees):
    import jax

    return jax.tree.map(fn, *trees)


def check(ctx, inputs, trainer, record) -> Tuple[Dict[str, float], List[str]]:
    import jax.numpy as jnp

    ref = correct.reference_module(ctx.config)
    spec, opt_cfg = trainer.spec, ctx.config["cfg"]
    learn_rate, weight_decay = float(opt_cfg["LEARN_RATE"]), float(opt_cfg["WEIGHT_DECAY"])
    warmup = int(opt_cfg.get("WARMUP_EPOCHS", 0))
    shape, share = ref.Shape.of(inputs.model), ref.Share(spec.first, spec.held)
    block, bias = min(BLOCK, spec.length), trainer.route_bias
    batches = inputs.tokens.reshape(-1, spec.batch, spec.length)
    losses, warm = record["losses"], record["warmup_params"]

    faults = [f"weights {leaf} are as the warm-up left them after {record['epochs']} more epochs"
              for leaf in correct.unmoved_leaves(warm, program.host_params(trainer))]
    trainer.params = trainer.opt_state = None  # the window's state: room for what follows

    # the eval forward at the warm-up's weights, on the window's last batch
    last = batches[(len(losses) - 1) % len(batches)]
    rows = sample_rows(ctx.seed, spec.tokens)
    warm_dev = _tree(jnp.asarray, warm)
    logits, choice = trainer._eval_logits(warm_dev, bias, jnp.asarray(last), jnp.asarray(rows))
    choice = _choice_by_sequence(choice, spec.batch)
    ref_logits, own = reference_at_rows(ref, warm_dev, last, rows, choice, shape, share, bias, block)
    errors = {
        "logits_rel": correct.relative_error(np.asarray(logits), ref_logits),
        "route_mismatch": mismatched(choice, own) / float(np.prod(own.shape[:-1])),
    }
    del warm_dev

    # the first steps, replayed from the seed's initial state and followed
    params, opt_state = trainer.initial_state()
    zeros = _tree(np.zeros_like, _host(ref.tail_of(params)))
    m_before, ref_m, ref_v = zeros, zeros, zeros
    loss_errors, grad_errors, update_errors = [], [], []
    for step in range(min(FOLLOWED_STEPS, len(losses))):
        batch = batches[step % len(batches)]
        held = _host(params)  # the weights before the step: the step consumes its arguments
        before = ref.tail_of(held)
        params, opt_state, loss, _, choice = trainer._train_step(
            params, opt_state, bias, trainer.corpus, trainer._batch_index[step % len(batches)])
        if float(loss) != losses[step]:
            faults.append(f"step {step} replayed from the seed's state gives the loss "
                          f"{float(loss)!r}, the run recorded {losses[step]!r}")
        ref_loss, ref_grads = ref.tail_loss_and_grads(
            _tree(jnp.asarray, held), batch, shape, share, bias,
            _choice_by_sequence(choice, spec.batch), block)
        ref_loss, ref_grads = float(ref_loss), _host(ref_grads)
        del held
        loss_errors.append(abs(losses[step] - ref_loss) / abs(ref_loss))
        after, m_after = _host(ref.tail_of(params)), _host(ref.tail_of(opt_state.m))
        grads = _tree(lambda m1, m0, w: (m1 - BETA1 * m0) / (1.0 - BETA1) - weight_decay * w,
                      m_after, m_before, before)
        grad_errors.append(correct.gradient_error(grads, ref_grads))
        runtime.log(f"step {step} grads by leaf: {_worst_leaves(grads, ref_grads)}")
        ref_after, ref_m, ref_v = adam_tree(
            ref, before, ref_grads, ref_m, ref_v, step + 1, learn_rate, weight_decay, warmup)
        moved = _tree(lambda a, b: a.astype(np.float64) - b, after, before)
        ref_moved = _tree(lambda a, b: a - b, ref_after, before)
        update_errors.append(correct.gradient_error(moved, ref_moved))
        runtime.log(f"step {step} updates by leaf: {_worst_leaves(moved, ref_moved)}")
        m_before = m_after
        runtime.log(f"followed step {step}: loss {losses[step]:.6f} against {ref_loss:.6f}, "
                    f"grads_rel {grad_errors[-1]:.4f}, update_rel {update_errors[-1]:.4f}")
    trainer.params, trainer.opt_state = params, opt_state
    errors.update(loss_rel=max(loss_errors), grads_rel=max(grad_errors),
                  update_rel=max(update_errors))
    return errors, faults


# ---- the control: the reference in the program's place, a precision lower

CONTROL_DTYPE = {"bfloat16": "float8_e4m3fn", "float32": "bfloat16"}


def control_params(model: dict, layers: int, held: int, vocab: int, seed: int):
    """The control's own seeded weights in the program's layout (normal,
    std 0.02, norms at one): no trainer is built."""
    rng = np.random.default_rng(seed)
    d, h = int(model["hidden_size"]), int(model["num_attention_heads"])
    nope, rope = int(model["qk_nope_head_dim"]), int(model["qk_rope_head_dim"])
    rank, v_head = int(model["kv_lora_rank"]), int(model["v_head_dim"])
    width, routed = int(model["moe_intermediate_size"]), int(model["n_routed_experts"])

    def normal(*shape):
        return rng.standard_normal(shape, dtype=np.float32) * np.float32(0.02)

    def attention(*lead):
        return {
            "norm1": np.ones(lead + (d,), np.float32), "wq": normal(*lead, d, h * (nope + rope)),
            "wkv_a": normal(*lead, d, rank + rope), "kv_norm": np.ones(lead + (rank,), np.float32),
            "wkv_b": normal(*lead, rank, h * (nope + v_head)), "wo": normal(*lead, h * v_head, d),
            "norm2": np.ones(lead + (d,), np.float32),
        }

    ffn, shared = int(model["intermediate_size"]), int(model["n_shared_experts"]) * width
    n = layers - 1
    return {
        "embed": normal(vocab, d),
        "dense": {**attention(), "wg": normal(d, ffn), "wu": normal(d, ffn), "wd": normal(ffn, d)},
        "moe": {**attention(n), "router": normal(n, d, routed),
                "eg": normal(n, held, d, width), "eu": normal(n, held, d, width),
                "ed": normal(n, held, width, d),
                "sg": normal(n, d, shared), "su": normal(n, d, shared), "sd": normal(n, shared, d)},
        "norm": np.ones((d,), np.float32), "head": normal(d, vocab),
    }


def control(ctx) -> Dict[str, float]:
    """The errors ``check`` would return if the program were the plain
    reference computed in the nearest precision below the one the
    configuration states (fp8 products for bfloat16): the step that would
    tempt a later PR. By the measures and at the size of the check itself,
    at the control's own seeded weights, on one batch: its logits and
    choices against the float32 reference following them, and its first
    step (loss, gradients of the tail, Adam's update from them). They have
    to fail the configuration's limits (benchmark/control.py)."""
    import jax.numpy as jnp

    from harness import spec as harness_spec

    config = ctx.config
    inputs_of = harness_spec.config_module(config, "inputs")
    ref = correct.reference_module(config)
    model = inputs_of.program_model(config, ctx.rehearse)
    cfg = dict(config["cfg"], **(config["rehearse"].get("cfg", {}) if ctx.rehearse else {}))
    held = int(model["n_routed_experts"]) // int(cfg["EXPERT_SHARDS"])
    vocab = int(model["vocab_size"]) // int(cfg["VOCAB_SHARDS"])
    length, sequences = int(cfg["SEQ_LENGTH"]), int(cfg["SEQ_BATCH"])
    learn_rate, weight_decay = float(cfg["LEARN_RATE"]), float(cfg["WEIGHT_DECAY"])
    warmup = int(cfg.get("WARMUP_EPOCHS", 0))
    shape, share = ref.Shape.of(model), ref.Share(int(cfg["EXPERT_SHARD"]) * held, held)
    block = min(BLOCK, length)
    dtype = getattr(jnp, CONTROL_DTYPE[str(cfg.get("PRECISION", "float32"))])

    batch = inputs_of.make_tokens(sequences, length, vocab, ctx.seed)
    params = _tree(jnp.asarray, control_params(model, int(cfg["SEQ_LAYERS"]), held, vocab, ctx.seed))
    rows = sample_rows(ctx.seed, sequences * length)
    low_logits, low_choice = reference_at_rows(ref, params, batch, rows, None, shape, share, None,
                                               block, dtype)
    ref_logits, own = reference_at_rows(ref, params, batch, rows, low_choice, shape, share, None, block)
    errors = {
        "logits_rel": correct.relative_error(low_logits, ref_logits),
        "route_mismatch": mismatched(low_choice, own) / float(np.prod(own.shape[:-1])),
    }
    low_loss, low_grads = ref.tail_loss_and_grads(params, batch, shape, share, None, low_choice,
                                                  block, dtype)
    ref_loss, ref_grads = ref.tail_loss_and_grads(params, batch, shape, share, None, low_choice, block)
    low_grads, ref_grads = _host(low_grads), _host(ref_grads)
    before = _host(ref.tail_of(params))
    zeros = _tree(np.zeros_like, before)
    low_after, _, _ = adam_tree(ref, before, low_grads, zeros, zeros, 1, learn_rate, weight_decay, warmup)
    ref_after, _, _ = adam_tree(ref, before, ref_grads, zeros, zeros, 1, learn_rate, weight_decay, warmup)
    errors.update(
        loss_rel=abs(float(low_loss) - float(ref_loss)) / abs(float(ref_loss)),
        grads_rel=correct.gradient_error(low_grads, ref_grads),
        update_rel=correct.gradient_error(_tree(lambda a, b: a - b, low_after, before),
                                          _tree(lambda a, b: a - b, ref_after, before)),
    )
    return errors
