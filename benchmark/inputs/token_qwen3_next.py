"""Inputs of a token-sequence cell over a ``qwen3_next`` file (gated
delta-rule layers, a gated grouped-query attention layer every
``full_attention_interval``, every layer with experts): a seeded corpus of
token ids and the program's SEQLM trainer over it, as
``inputs/token_corpus.py`` builds them for a DeepSeek-V3 file, from which
the corpus' generator is taken.

The configuration's file holds the model under the source's own
``config.json`` keys, the counts this chip holds in place of the published
ones (``reduced``; the published ones under ``published``) and the
program's cfg keys for the cut (``cfg``). ``program_model`` puts the
published counts back; a rehearsal lays its toy sizes over them.
"""

from __future__ import annotations

import json
import os
import time
from typing import Any, Dict

from harness import program, spec

_corpus = spec.named_module("inputs", "token_corpus")
Inputs, make_tokens, NOT_OF_THE_MODEL = _corpus.Inputs, _corpus.make_tokens, _corpus.NOT_OF_THE_MODEL

# the rehearsal's toy sizes, under this module's names -> config.json's keys
REHEARSAL_KEYS = {
    "hidden": "hidden_size", "heads": "num_attention_heads", "kv_heads": "num_key_value_heads",
    "head": "head_dim", "delta_key_heads": "linear_num_key_heads",
    "delta_value_heads": "linear_num_value_heads", "dense_ffn": "intermediate_size",
    "expert_ffn": "moe_intermediate_size", "shared_ffn": "shared_expert_intermediate_size",
    "routed": "num_experts", "per_token": "num_experts_per_tok", "vocab": "vocab_size",
    "positions": "max_position_embeddings",
}
REHEARSAL_DELTA_DIM = ("linear_key_head_dim", "linear_value_head_dim")  # one toy size, ``delta_dim``


def program_model(config: dict, rehearse: bool) -> Dict[str, Any]:
    """The model as the source publishes it (the counts this chip holds
    replaced by the published ones); a rehearsal's toy sizes over it."""
    model = {k: v for k, v in config.items() if k not in NOT_OF_THE_MODEL}
    model.update({k: v for k, v in config["published"].items() if k in model})
    if rehearse:
        toy = config["rehearse"]["model"]
        model.update({REHEARSAL_KEYS[k]: v for k, v in toy.items() if k in REHEARSAL_KEYS})
        model.update({k: toy["delta_dim"] for k in REHEARSAL_DELTA_DIM})
    return model


def build(ctx):
    from neutronstarlite_tpu.models.seqlm import SeqLMTrainer, SeqSpec

    config = ctx.config
    model = program_model(config, ctx.rehearse)
    model_path = os.path.join(ctx.work_dir, "model.json")
    with open(model_path, "w") as fh:
        json.dump(model, fh)
    cfg = program.read_cfg(config, ctx.work_dir, ctx.rehearse, {"MODEL_FILE": model_path})
    spec_ = SeqSpec.from_cfg(model, cfg)

    t = time.perf_counter()
    tokens = make_tokens(cfg.seq_corpus * spec_.batch, spec_.length, spec_.vocab, ctx.seed)
    ctx.spans["datum_s"] = time.perf_counter() - t

    t = time.perf_counter()
    trainer = SeqLMTrainer.from_tokens(cfg, tokens, seed=ctx.seed % (2 ** 31))
    ctx.spans["trainer_build_s"] = time.perf_counter() - t
    traffic = getattr(ctx, "traffic", None) or {}
    return Inputs(tokens, model, int(traffic.get("warmup_epochs", 0)),
                  bool(getattr(ctx, "trace", False))), trainer


def shape(inputs: Inputs, trainer) -> dict:
    """What ``needs/qwen3_next.py`` counts from: what a token-sequence
    cell's shape holds (``inputs/token_corpus.py``: published sizes, this
    chip's share, the step's batch, the rows routed to held experts, the
    scope table of a traced run; ``nope + rope`` is a query head's dims)
    and the mixer of every kept layer, the attention's key/value heads, the
    delta rule's sizes and, from the program's counters, the (token, layer)
    pairs a step walks through each mixer."""
    spec_ = trainer.spec
    gauges = trainer.metrics.snapshot(include_hists=False)["gauges"]
    steps = max(len(trainer.loss_history), 1)
    return dict(
        _corpus.shape(inputs, trainer),
        mixers=list(spec_.mixers), kda_layers=int(gauges["seq.kda_layers"]),
        gqa_layers=int(gauges["seq.gqa_layers"]), kv_heads=spec_.kv_heads,
        kda_value_heads=int(gauges["kda.value_heads"]), kda_key_heads=int(gauges["kda.key_heads"]),
        kda_dim=spec_.kda_dim, conv_kernel=spec_.conv_kernel, kda_chunk=int(gauges["kda.chunk"]),
        kda_decay_per_head=int(gauges["kda.decay_per_head"]),
        kda_token_layers=program.counter(trainer, "kda.token_layers") / steps,
        gqa_token_layers=program.counter(trainer, "gqa.token_layers") / steps,
    )
