"""Check of the fixture's cell: the stub trainer's logits at the weights
the window ends on, and its loss gradients at the weights the warm-up
ended on, against the plain reference."""

from __future__ import annotations

import numpy as np

from harness import correct, program


def check(ctx, tokens, trainer, record):
    ref = correct.reference_module(ctx.config)
    params, warm = program.host_params(trainer), record["warmup_params"]
    faults = [] if tokens.max() < trainer.params["out"].shape[1] else ["a token id beyond the vocabulary"]
    errors = {"logits_rel": correct.relative_error(
        np.asarray(trainer.logits(trainer.params, tokens)), ref.logits(params, tokens))}
    _, grads = trainer.loss_and_grads(warm, tokens)
    _, ref_grads = ref.loss_and_grads(warm, tokens)
    errors["grads_rel"] = correct.gradient_error(
        {k: np.asarray(v) for k, v in grads.items()}, ref_grads)
    return errors, faults
