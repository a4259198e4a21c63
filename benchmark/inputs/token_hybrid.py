"""Inputs of a token-sequence cell whose stack has two kinds of mixer (a
``kimi_linear`` file): a seeded corpus of token ids and the program's SEQLM
trainer over it, as ``inputs/token_corpus.py`` builds them for a
DeepSeek-V3 file, from which the corpus' generator is taken.

The configuration's file holds the model under the source's own
``config.json`` keys, the counts this chip holds in place of the published
ones (``reduced``; the published ones under ``published``) and the
program's cfg keys for the cut (``cfg``). ``program_model`` puts the
published counts back; a rehearsal lays its toy sizes over them, the toy
delta-rule heads inside ``linear_attn_config``, whose layer lists stay the
published ones (so the kept layers keep the published order of mixers).
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
    "hidden": "hidden_size", "heads": "num_attention_heads", "latent": "kv_lora_rank",
    "nope": "qk_nope_head_dim", "shared": "qk_rope_head_dim", "value": "v_head_dim",
    "dense_ffn": "intermediate_size", "expert_ffn": "moe_intermediate_size",
    "routed": "num_experts", "per_token": "num_experts_per_token", "vocab": "vocab_size",
    "positions": "model_max_length",
}
REHEARSAL_LINEAR_KEYS = {"kda_heads": "num_heads", "kda_dim": "head_dim"}


def program_model(config: dict, rehearse: bool) -> Dict[str, Any]:
    """The model as the source publishes it (the counts this chip holds
    replaced by the published ones); a rehearsal's toy sizes over it."""
    model = {k: v for k, v in config.items() if k not in NOT_OF_THE_MODEL}
    model.update({k: v for k, v in config["published"].items() if k in model})
    if rehearse:
        toy = config["rehearse"]["model"]
        model.update({REHEARSAL_KEYS[k]: v for k, v in toy.items() if k in REHEARSAL_KEYS})
        model["linear_attn_config"] = dict(
            model["linear_attn_config"],
            **{REHEARSAL_LINEAR_KEYS[k]: v for k, v in toy.items() if k in REHEARSAL_LINEAR_KEYS})
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
    """What ``needs/kimi_linear.py`` counts from: what a token-sequence
    cell's shape holds (``inputs/token_corpus.py``: published sizes, this
    chip's share, the step's batch, the rows routed to held experts, the
    scope table of a traced run) and the mixer of every kept layer, the
    KDA's sizes and, from the program's counter, the (token, KDA layer)
    pairs a step walks."""
    spec_ = trainer.spec
    gauges = trainer.metrics.snapshot(include_hists=False)["gauges"]
    return dict(
        _corpus.shape(inputs, trainer),
        mixers=list(spec_.mixers), kda_layers=int(gauges["seq.kda_layers"]),
        mla_layers=int(gauges["seq.mla_layers"]), kda_heads=spec_.kda_heads,
        kda_dim=spec_.kda_dim, conv_kernel=spec_.conv_kernel, kda_chunk=int(gauges["kda.chunk"]),
        kda_token_layers=(program.counter(trainer, "kda.token_layers")
                          / max(len(trainer.loss_history), 1)),
    )
