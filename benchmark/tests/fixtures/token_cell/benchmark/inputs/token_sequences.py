"""Inputs of the fixture's cell: seeded sequences of token ids [count,
length] int32, and the stub trainer over them. No graph, no float feature,
no mask: ``datum_s`` and ``trainer_build_s`` are the spans that apply."""

from __future__ import annotations

import time

import numpy as np


def build(ctx):
    from stub_program.token_trainer import TokenTrainer

    seq, model = ctx.config["sequences"], ctx.config["model"]
    t = time.perf_counter()
    rng = np.random.default_rng(ctx.seed)
    tokens = rng.integers(0, seq["vocab"], size=(seq["count"], seq["length"]), dtype=np.int32)
    ctx.spans["datum_s"] = time.perf_counter() - t

    t = time.perf_counter()
    trainer = TokenTrainer(tokens, seq["vocab"], model["width"], model["learn_rate"],
                           ctx.seed, ctx.config["stub"]["fault"])
    ctx.spans["trainer_build_s"] = time.perf_counter() - t
    return tokens, trainer


def shape(tokens, trainer) -> dict:
    vocab, width = trainer.params["out"].shape[1], trainer.params["out"].shape[0]
    return {"tokens": int(tokens.shape[0] * (tokens.shape[1] - 1)), "vocab": int(vocab),
            "width": int(width), "itemsize": 4}
