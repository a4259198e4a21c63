"""Trainers: the warm-up ``run()``'s ``final_eval`` span: the closing
eval-mode forward, its logits copy and the host accuracy. None where the
configuration turns the closing evaluation off."""

from harness import program_spans


def read(ctx, record):
    return program_spans.first_seconds("final_eval")
