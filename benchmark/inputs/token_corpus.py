"""Inputs of a token-sequence cell: a seeded corpus of token ids and the
program's SEQLM trainer over it.

The configuration's file holds the model under the source's own
``config.json`` keys, the counts this chip holds in place of the published
ones (``reduced``; the published ones under ``published``) and the program's
cfg keys for the cut (``cfg``). The program wants the published
``config.json`` and the cut apart: ``program_model`` puts the published
counts back, writes the model file the cfg's MODEL_FILE names, and the
trainer is built through the program's own funnel: cfg file -> ``InputInfo``
-> ``from_tokens`` (the entry ``from_arrays`` is for the vertex families).
Token ids are uniform over the vocabulary slice, from ``--seed``; the
weights come from the seed through the program's own initialiser.
"""

from __future__ import annotations

import json
import os
import time
from typing import Any, Dict, NamedTuple

import numpy as np

from harness import program, runtime

# the rehearsal's toy sizes, under this module's names -> config.json's keys
REHEARSAL_KEYS = {
    "hidden": "hidden_size", "heads": "num_attention_heads", "latent": "kv_lora_rank",
    "nope": "qk_nope_head_dim", "rope": "qk_rope_head_dim", "value": "v_head_dim",
    "dense_ffn": "intermediate_size", "expert_ffn": "moe_intermediate_size",
    "routed": "n_routed_experts", "per_token": "num_experts_per_tok", "vocab": "vocab_size",
    "positions": "max_position_embeddings",
}
NOT_OF_THE_MODEL = (
    "name", "source", "deployment", "inputs", "check", "need", "reference", "env", "published",
    "held", "batch", "cfg", "reduced", "assumed", "tolerance", "rehearse", "memory",
)


class Inputs(NamedTuple):
    tokens: np.ndarray  # [corpus batches * sequences a step, length] int32
    model: Dict[str, Any]  # the published config.json the program was given
    warmup_epochs: int
    traced: bool


def program_model(config: dict, rehearse: bool) -> Dict[str, Any]:
    """The model as the source publishes it (the counts this chip holds
    replaced by the published ones); a rehearsal's toy sizes over it."""
    model = {k: v for k, v in config.items() if k not in NOT_OF_THE_MODEL}
    model.update({k: v for k, v in config["published"].items() if k in model})
    if rehearse:
        toy = config["rehearse"]["model"]
        model.update({REHEARSAL_KEYS[k]: v for k, v in toy.items() if k in REHEARSAL_KEYS})
    return model


def make_tokens(sequences: int, length: int, vocab: int, seed: int) -> np.ndarray:
    return np.random.default_rng(seed).integers(0, vocab, size=(sequences, length), dtype=np.int32)


def build(ctx):
    from neutronstarlite_tpu.models.seqlm import SeqLMTrainer, SeqSpec

    config = ctx.config
    model = program_model(config, ctx.rehearse)
    model_path = os.path.join(ctx.work_dir, "model.json")
    with open(model_path, "w") as fh:
        json.dump(model, fh)
    cfg = program.read_cfg(config, ctx.work_dir, ctx.rehearse, {"MODEL_FILE": model_path})
    spec = SeqSpec.from_cfg(model, cfg)

    t = time.perf_counter()
    tokens = make_tokens(cfg.seq_corpus * spec.batch, spec.length, spec.vocab, ctx.seed)
    ctx.spans["datum_s"] = time.perf_counter() - t

    t = time.perf_counter()
    trainer = SeqLMTrainer.from_tokens(cfg, tokens, seed=ctx.seed % (2 ** 31))
    ctx.spans["trainer_build_s"] = time.perf_counter() - t
    traffic = getattr(ctx, "traffic", None) or {}
    return Inputs(tokens, model, int(traffic.get("warmup_epochs", 0)),
                  bool(getattr(ctx, "trace", False))), trainer


def shape(inputs: Inputs, trainer) -> dict:
    """What ``needs/moonlight.py`` counts from: the published sizes, this
    chip's share, the step's batch and, from the program's counter, the
    (token, expert) pairs an epoch of the window sent to held experts
    (mean over the window's epochs, all layers)."""
    import jax

    spec = trainer.spec
    routed = trainer.routed_history[inputs.warmup_epochs:] or trainer.routed_history
    gauges = trainer.metrics.snapshot(include_hists=False)["gauges"]
    runtime.log(f"rows routed to held experts by epoch {trainer.routed_history}; the program's "
                f"epoch seconds {[round(t, 4) for t in trainer.epoch_times]}")
    # instruction name -> scope of the compiled step, for the readers that
    # sum the trace by scope (a traced run alone pays for the lookup)
    table = trainer.scope_table() if inputs.traced else None
    return {
        "scope_table": table,
        "hidden": spec.hidden, "heads": spec.heads, "kv_rank": spec.kv_rank, "nope": spec.nope,
        "rope": spec.rope, "v_head": spec.v_head, "ffn": spec.ffn,
        "expert_width": spec.expert_width, "shared_width": spec.shared_width,
        "routed": spec.routed, "per_token": spec.per_token, "held": spec.held,
        "moe_layers": spec.moe_layers, "vocab": spec.vocab, "length": spec.length,
        "sequences": spec.batch, "tokens": spec.tokens,
        "parameters": int(sum(np.prod(a.shape) for a in jax.tree.leaves(trainer.params))),
        "itemsize": 2 if trainer.cfg.precision == "bfloat16" else 4,
        "routed_rows": float(np.mean(routed)) if routed else None,
        "moe_load_max_over_mean": gauges.get("moe.load_max_over_mean"),
    }

