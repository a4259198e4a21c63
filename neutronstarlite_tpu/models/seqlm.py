"""The token-sequence trainer family (ALGORITHM:SEQLM): next-token training
of a stack of layers, each a token mixer of one of four kinds (latent
attention, with or without rotary positions; grouped-query softmax
attention, with an output gate or without; a gated delta-rule linear
attention, its decay per key channel or per head; a gated short
convolution) followed by an MLP of one of two kinds (dense; routed experts,
the router a sigmoid or a softmax, with a shared expert, gated or not, or
none), over an integer datum and an implicit causal graph.

Beside ``fullbatch``, ``dist`` and ``sampled`` this is the fourth run loop
on ``ToolkitBase``. What differs from them is the datum and the graph: the
datum is token ids (graph/dataset.TokenDatum), the graph is never built
(vertex ``i`` of a sequence has an in-edge from every ``j <= i``:
ops/causal_attention.py enumerates its tiles), and a second graph, token ->
expert, is drawn anew inside every step (ops/moe.py). What is shared is the
funnel (``init_graph`` / ``init_nn`` / ``_finalize_datum`` /
``build_model``), the live spans, ``emit_epoch``, checkpoints,
``finalize_metrics``, the optimizer (nn/param.py) and the compute cast.

**An epoch is one optimizer step over one batch** of SEQ_BATCH sequences;
batches cycle through a corpus of SEQ_CORPUS resident on the device.

The model is described by the source's own ``config.json`` keys in the JSON
file MODEL_FILE names, in one of four dialects, each one record of
``DIALECTS`` (``Dialect``: what tells it apart, its keys, what the family is
written for in it, how it fills ``SeqSpec``; ``SeqSpec.from_cfg`` reads
through it): a DeepSeek-V3 file is the stack "latent attention with rotary
in every layer"; a ``kimi_linear`` file names the mixer of every layer
(``linear_attn_config.kda_layers`` / ``full_attn_layers``) and its latent
attention rotates nothing; a ``qwen3_next`` file has every
``full_attention_interval``-th layer attend (grouped queries, an output
gate, rotary over a leading part of a head), the others run the delta rule
with a decay per head and twice the value heads, every layer routes
(softmax over the experts, a gated shared expert, no leading dense layer)
and every norm but the delta rule's output norm is zero-centred; an
``lfm2_moe`` file names the mixer of every layer (``layer_types``: a gated
short convolution or grouped-query attention without a gate, rotary over
the whole head), leads with ``num_dense_layers`` dense layers, and routes
the rest by a sigmoid with a selection bias and no shared expert. The cut
to one chip's share is in cfg keys: SEQ_LAYERS (layers kept, the leading
dense ones first), EXPERT_SHARDS /
EXPERT_SHARD (this chip holds ``n_routed_experts / EXPERT_SHARDS`` experts
of every layer, routes over all of them and computes its own experts' part;
what absent experts would add is left out and nothing stands in for their
chips), VOCAB_SHARDS (ids, logits and loss over this chip's slice),
SEQ_LENGTH, SEQ_BATCH.

The step (one jitted program): embed; the leading dense layers, each under
its own key (``dense``, then ``dense1``, ...); the expert layers in runs of
one mixer kind, each run stacked on a leading axis under its own key
(``moe``, then ``moe1``, ``moe2``, ...) and walked by one ``lax.scan`` (a
stack of one kind is the single run ``moe``); every layer recomputed in the
backward (``jax.checkpoint``); the head and the loss in chunks of tokens
(the ``[tokens, vocab]`` logits never exist whole); Adam, a run's routed
experts updated as soon as that run's backward is done (``_step``).
PRECISION:bfloat16 computes the products in bfloat16 over the float32
masters; norms, rotary, the softmax state, the delta rule's decay, system
and state, the short convolution and its two gates, the router (its
product, scores and top-k) and the residual stream stay float32.

Named scopes of the step (``SCOPES``): ``scope_table()`` hands out, for the
compiled step, instruction name -> scope, read from the HLO text's
``op_name``; the profiler's trace names device events by instruction, so a
reader can sum device time by scope.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import math
import re
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from neutronstarlite_tpu.graph.dataset import TokenDatum
from neutronstarlite_tpu.models.base import ToolkitBase, register_algorithm
from neutronstarlite_tpu.nn import seq as nnseq
from neutronstarlite_tpu.nn.layers import compute_cast
from neutronstarlite_tpu.nn.param import AdamConfig, AdamState, adam_init, adam_update
from neutronstarlite_tpu.ops import causal_attention, delta_rule, moe
from neutronstarlite_tpu.ops.causal_attention import causal_edge_attention
from neutronstarlite_tpu.ops.conv_operand import conv_operand
from neutronstarlite_tpu.resilience.faults import fault_point
from neutronstarlite_tpu.utils.config import InputInfo
from neutronstarlite_tpu.utils.logging import get_logger
from neutronstarlite_tpu.utils.timing import get_time

log = get_logger("seqlm")

SCOPES = (
    "seq/embed", "seq/mla/project", "seq/mla/attend", "seq/gqa/project", "seq/gqa/attend",
    "seq/kda/project", "seq/kda/conv",
    "seq/kda/gate", "seq/kda/recur", "seq/kda/out", "seq/sconv/project", "seq/sconv/conv",
    "seq/sconv/out", "seq/dense_mlp",
    "seq/moe/route", "seq/moe/dispatch", "seq/moe/experts", "seq/moe/shared",
    "seq/moe/combine", "seq/head_loss", "seq/adam",
)
DEFAULT_LOSS_CHUNK = 4096
INIT_STD = 0.02  # assumed: config.json gives no initializer_range


def _read(model: dict, key: str, default: Any = None) -> Any:
    """``model[key]``, a dotted key read down its nested groups
    (``rope_parameters.rope_theta``); ``default`` where a level is absent."""
    for part in key.split("."):
        if not isinstance(model, dict) or part not in model:
            return default
        model = model[part]
    return model


@dataclasses.dataclass(frozen=True)
class Dialect:
    """One dialect of ``config.json`` as one record: what tells it apart
    (``is_of``), the file's key for each of the family's counts (``keys``,
    dotted where the file nests it), what the family is written for in it
    (``written_for``: key, the value computed; a file that says otherwise is
    refused by the key's name), and how it fills ``SeqSpec``: its leading
    dense layers, the mixer of every kept layer and the sizes and options
    that are its own (``sizes``, which refuses what does not share evenly)."""

    name: str
    is_of: Callable[[dict], bool]
    keys: Dict[str, str]
    written_for: Tuple[Tuple[str, Any], ...]
    dense: Callable[[dict], int]
    mixers: Callable[[dict, int], Tuple[str, ...]]
    sizes: Callable[[dict], Dict[str, Any]]


def _keys_of(**own: str) -> Dict[str, str]:
    """A dialect's keys: DeepSeek-V3's names, ``own`` over them."""
    return dict(dict(routed="n_routed_experts", per_token="num_experts_per_tok",
                     renormalised="norm_topk_prob", positions="max_position_embeddings",
                     eps="rms_norm_eps", theta="rope_theta"), **own)


LATENT_ONLY = (("q_lora_rank", None), ("topk_group", 1), ("moe_layer_freq", 1))


def _leading_dense(model: dict) -> int:
    return int(model.get("first_k_dense_replace", 1))


def _latent_sizes(model: dict) -> Dict[str, Any]:
    linear = model.get("linear_attn_config")
    return dict(
        kv_rank=int(model["kv_lora_rank"]), nope=int(model["qk_nope_head_dim"]),
        rope=int(model["qk_rope_head_dim"]), v_head=int(model["v_head_dim"]),
        shared_width=(int(model["num_shared_experts" if linear else "n_shared_experts"])
                      * int(model["moe_intermediate_size"])),
        route_scale=float(model["routed_scaling_factor"]), rotary=not linear,
        kda_heads=int(linear["num_heads"]) if linear else 0,
        kda_value_heads=int(linear["num_heads"]) if linear else 0,
        kda_dim=int(linear["head_dim"]) if linear else 0,
        conv_kernel=int(linear["short_conv_kernel_size"]) if linear else 0,
    )


def _kimi_mixers(model: dict, layers: int) -> Tuple[str, ...]:
    linear, published = model["linear_attn_config"], int(model["num_hidden_layers"])
    kda, full = linear["kda_layers"], linear["full_attn_layers"]  # 1-based
    if sorted(kda + full) != list(range(1, published + 1)):
        raise ValueError(
            f"MODEL_FILE's linear_attn_config.kda_layers and full_attn_layers name "
            f"{sorted(kda + full)}: not each of num_hidden_layers={published} layers once"
        )
    return tuple("kda" if i + 1 in kda else "mla" for i in range(layers))


def _qwen3_next_mixers(model: dict, layers: int) -> Tuple[str, ...]:
    every = int(model["full_attention_interval"])
    return tuple("gqa" if (i + 1) % every == 0 else "kda" for i in range(layers))


def _qwen3_next_sizes(model: dict) -> Dict[str, Any]:
    head = int(model["head_dim"])
    turned = int(round(head * float(model["partial_rotary_factor"])))
    heads, kv_heads = int(model["num_attention_heads"]), int(model["num_key_value_heads"])
    key_dim, value_dim = int(model["linear_key_head_dim"]), int(model["linear_value_head_dim"])
    key_heads, value_heads = int(model["linear_num_key_heads"]), int(model["linear_num_value_heads"])
    if heads % kv_heads or value_heads % key_heads or key_dim != value_dim or turned % 2:
        raise ValueError(
            f"MODEL_FILE's heads do not share evenly ({heads} query heads over {kv_heads} "
            f"key/value heads, {value_heads} delta-rule value heads over {key_heads} key "
            f"heads), its delta-rule key and value dims differ ({key_dim}, {value_dim}) or "
            f"rotary turns an odd number of dims ({turned})")
    return dict(
        kv_rank=0, nope=head - turned, rope=turned, v_head=head,
        shared_width=int(model["shared_expert_intermediate_size"]), route_scale=1.0,
        kda_heads=key_heads, kda_value_heads=value_heads, kda_dim=key_dim,
        conv_kernel=int(model["linear_conv_kernel_dim"]), decay_per_head=True,
        gates_low_rank=False, out_gate="silu", kv_heads=kv_heads, centred_norms=True,
        scoring="softmax", shared_gate=True,
    )


LFM2_MIXERS = {"conv": "sconv", "full_attention": "gqa"}  # layer_types -> the family's mixer


def _lfm2_mixers(model: dict, layers: int) -> Tuple[str, ...]:
    types, published = list(model["layer_types"]), int(model["num_hidden_layers"])
    if len(types) != published:
        raise ValueError(f"MODEL_FILE's layer_types names {len(types)} layers of "
                         f"num_hidden_layers={published}")
    for kind in types:
        if kind not in LFM2_MIXERS:
            raise ValueError(f"MODEL_FILE's layer_types has {kind!r}; the SEQLM family computes "
                             f"{sorted(LFM2_MIXERS)}")
    return tuple(LFM2_MIXERS[kind] for kind in types[:layers])


def _lfm2_sizes(model: dict) -> Dict[str, Any]:
    hidden, heads = int(model["hidden_size"]), int(model["num_attention_heads"])
    kv_heads = int(model["num_key_value_heads"])
    head = hidden // heads  # the file has no head_dim: a head is hidden / heads
    if hidden % heads or heads % kv_heads or head % 2:
        raise ValueError(
            f"MODEL_FILE's heads do not share evenly ({heads} query heads of hidden {hidden}, "
            f"over {kv_heads} key/value heads) or a head has an odd number of dims ({head})")
    return dict(
        kv_rank=0, nope=0, rope=head, v_head=head, shared_width=0,
        route_scale=float(model["routed_scaling_factor"]), route_eps=1e-6,
        conv_kernel=int(model["conv_L_cache"]), kv_heads=kv_heads, attn_gate=False,
    )


DIALECTS = (
    Dialect("lfm2_moe", lambda m: m.get("model_type") == "lfm2_moe",
            _keys_of(routed="num_experts", eps="norm_eps", theta="rope_parameters.rope_theta"),
            (("conv_bias", False), ("use_expert_bias", True), ("tie_word_embeddings", False),
             ("block_auto_adjust_ff_dim", False), ("rope_parameters.rope_type", "default"),
             ("rope_scaling", None)),
            lambda m: int(m["num_dense_layers"]), _lfm2_mixers, _lfm2_sizes),
    Dialect("qwen3_next",
            lambda m: m.get("model_type") == "qwen3_next" or "full_attention_interval" in m,
            _keys_of(routed="num_experts"),
            (("mlp_only_layers", []), ("decoder_sparse_step", 1), ("rope_scaling", None),
             ("use_sliding_window", False)),
            lambda m: 0, _qwen3_next_mixers, _qwen3_next_sizes),
    Dialect("kimi_linear", lambda m: bool(m.get("linear_attn_config")),
            _keys_of(routed="num_experts", per_token="num_experts_per_token",
                     renormalised="moe_renormalize", positions="model_max_length"),
            LATENT_ONLY + (("num_expert_group", 1), ("moe_router_activation_func", "sigmoid"),
                           ("mla_use_nope", True)),
            _leading_dense, _kimi_mixers, _latent_sizes),
    Dialect("deepseek_v3", lambda m: True, _keys_of(),
            LATENT_ONLY + (("n_group", 1), ("scoring_func", "sigmoid")),
            _leading_dense, lambda m, layers: ("mla",) * layers, _latent_sizes),
)


def dialect_of(model: dict) -> Dialect:
    """The first of ``DIALECTS`` that takes the file (DeepSeek-V3's takes
    any)."""
    return next(d for d in DIALECTS if d.is_of(model))


@dataclasses.dataclass(frozen=True)
class SeqSpec:
    """The sizes the step is traced with: the published ones of
    ``config.json`` and this chip's share."""

    hidden: int
    heads: int
    kv_rank: int  # the latent attention's; 0 where the stack attends by grouped queries
    nope: int  # dims of a query head that no position turns
    rope: int  # dims that rotary turns: the latent attention's shared ones, a "gqa" head's leading ones
    v_head: int
    ffn: int  # a dense layer's width
    expert_width: int
    shared_width: int  # the shared expert's width; 0: the expert layer has none
    routed: int
    per_token: int
    route_scale: float
    theta: float
    eps: float
    moe_layers: int
    first: int  # first routed expert held here
    held: int  # routed experts held here
    vocab: int  # rows of the vocabulary held here
    length: int
    batch: int
    block: int
    loss_chunk: int
    mixers: tuple = ()  # the mixer of every kept layer: "mla", "gqa", "kda" or "sconv"
    rotary: bool = True  # whether the latent attention turns its shared dims by position
    # the delta-rule mixer's sizes; the ``kda_`` names also size the per-head-gated one
    kda_heads: int = 0  # its key (and query) heads
    kda_dim: int = 0  # key = value dims of a delta-rule head
    conv_kernel: int = 0  # taps of the delta rule's convolutions, or of the short convolution
    kda_chunk: int = 0  # positions a chunk of the delta rule
    kda_value_heads: int = 0  # its value heads: a key head serves value_heads / heads of them
    decay_per_head: bool = False  # one log-decay a value head and position, not one a key channel
    gates_low_rank: bool = True  # decay and output gate from rank-``kda_dim`` pairs, else full products
    out_gate: str = "sigmoid"  # the delta-rule output gate's activation: "sigmoid" or "silu"
    dense_layers: int = 1  # leading layers with a dense MLP: any number, 0 included
    kv_heads: int = 0  # key/value heads of a "gqa" layer
    centred_norms: bool = False  # norm weights as ``1 + w`` (the delta rule's output norm excepted)
    scoring: str = "sigmoid"  # the router's activation: "sigmoid" or "softmax"
    shared_gate: bool = False  # the shared expert behind its own sigmoid gate
    attn_gate: bool = True  # a "gqa" layer's output times sigmoid(gate), the gate from ``wq``
    route_eps: float = moe.ROUTE_EPS  # added to the chosen experts' sum before it divides them
    moe_chunk: int = 0  # places a step of the routed walk takes (0: ops/moe.chunk_of's own)

    @property
    def tokens(self) -> int:
        return self.batch * self.length

    @property
    def kda_layers(self) -> int:
        return self.mixers.count("kda")

    @property
    def runs(self) -> tuple:
        """The expert layers in runs of one mixer kind: (key of the run in
        the parameter tree, kind, first expert layer, layers)."""
        out = []
        for i, kind in enumerate(self.mixers[self.dense_layers:]):
            if out and out[-1][1] == kind:
                out[-1][3] += 1
            else:
                out.append([f"moe{len(out) or ''}", kind, i, 1])
        return tuple(tuple(r) for r in out)

    @staticmethod
    def from_cfg(model: dict, cfg: InputInfo) -> "SeqSpec":
        dialect = dialect_of(model)
        key = dialect.keys
        for name, want in dialect.written_for + ((key["renormalised"], True), ("hidden_act", "silu")):
            if _read(model, name, want) != want:
                raise ValueError(
                    f"MODEL_FILE has {name}={_read(model, name)!r}; the SEQLM family is written "
                    f"for {name}={want!r} (models/seqlm.py states the block it computes)"
                )
        dense = dialect.dense(model)
        published = int(model["num_hidden_layers"])
        layers = cfg.seq_layers or published
        if not dense + 1 <= layers <= published:
            kept = {0: "", 1: "the dense layer and "}.get(dense, f"the {dense} dense layers and ")
            raise ValueError(
                f"SEQ_LAYERS:{layers} must keep {kept}at least one expert layer of the model's "
                f"{model['num_hidden_layers']}"
            )
        mixers = dialect.mixers(model, layers)
        routed, vocab = int(model[key["routed"]]), int(model["vocab_size"])
        if routed % cfg.expert_shards or not 0 <= cfg.expert_shard < cfg.expert_shards:
            raise ValueError(
                f"EXPERT_SHARDS:{cfg.expert_shards} must divide the {routed} routed experts "
                f"and EXPERT_SHARD:{cfg.expert_shard} name one of the shards"
            )
        if vocab % cfg.vocab_shards:
            raise ValueError(f"VOCAB_SHARDS:{cfg.vocab_shards} must divide the vocabulary {vocab}")
        positions = int(model[key["positions"]])
        length = cfg.seq_length or positions
        if length > positions:
            raise ValueError(
                f"SEQ_LENGTH:{length} is beyond the model's {positions} positions")
        if cfg.attn_block and length % cfg.attn_block:
            raise ValueError(
                f"ATTN_BLOCK:{cfg.attn_block} does not divide the sequence length {length}")
        chunk = (cfg.kda_chunk or delta_rule.DEFAULT_CHUNK) if "kda" in mixers else 0
        if chunk and length % chunk:
            raise ValueError(f"KDA_CHUNK:{chunk} does not divide the sequence length {length}")
        held = routed // cfg.expert_shards
        tokens = cfg.seq_batch * length
        return SeqSpec(
            hidden=int(model["hidden_size"]), heads=int(model["num_attention_heads"]),
            ffn=int(model["intermediate_size"]), expert_width=int(model["moe_intermediate_size"]),
            routed=routed, per_token=int(model[key["per_token"]]),
            theta=float(_read(model, key["theta"])), eps=float(model[key["eps"]]),
            moe_layers=layers - dense, dense_layers=dense,
            first=cfg.expert_shard * held, held=held, vocab=vocab // cfg.vocab_shards,
            length=length, batch=cfg.seq_batch, block=cfg.attn_block,
            loss_chunk=math.gcd(tokens, cfg.loss_chunk or DEFAULT_LOSS_CHUNK),
            mixers=mixers, kda_chunk=chunk, moe_chunk=cfg.moe_chunk, **dialect.sizes(model),
        )


def _keys(key: jax.Array):
    """Keys without end: 31 of a split of 32, the last split again."""
    while True:
        *batch, key = jax.random.split(key, 32)
        yield from batch


def dense_key(i: int) -> str:
    """The parameter tree's key of the ``i``-th leading dense layer."""
    return f"dense{i or ''}"


def init_params(key: jax.Array, spec: SeqSpec) -> Dict[str, Any]:
    """Seeded weights, normal with std ``INIT_STD``, norms at one (at zero
    where the spec's norms are zero-centred, the delta rule's output norm
    at one either way); the leading dense layers one key each, the expert
    layers in runs of one mixer kind, each run stacked on a leading axis
    (its scan's)."""
    d, h = spec.hidden, spec.heads
    keys = _keys(key)

    def normal(*shape):
        return nnseq.normal_init(next(keys), shape, INIT_STD)

    def norm(*shape):
        return jnp.full(shape, 0.0 if spec.centred_norms else 1.0, jnp.float32)

    def mla(lead):
        return {
            "norm1": jnp.ones(lead + (d,), jnp.float32),
            "wq": normal(*lead, d, h * (spec.nope + spec.rope)),
            "wkv_a": normal(*lead, d, spec.kv_rank + spec.rope),
            "kv_norm": jnp.ones(lead + (spec.kv_rank,), jnp.float32),
            "wkv_b": normal(*lead, spec.kv_rank, h * (spec.nope + spec.v_head)),
            "wo": normal(*lead, h * spec.v_head, d),
            "norm2": jnp.ones(lead + (d,), jnp.float32),
        }

    def gqa(lead):
        head = spec.v_head
        return {
            "norm1": norm(*lead, d),
            # per head: the query, then its output gate where the layer has one
            "wq": normal(*lead, d, h * (2 if spec.attn_gate else 1) * head),
            "wk": normal(*lead, d, spec.kv_heads * head),
            "wv": normal(*lead, d, spec.kv_heads * head),
            "q_norm": norm(*lead, head), "k_norm": norm(*lead, head),
            "wo": normal(*lead, h * head, d),
            "norm2": norm(*lead, d),
        }

    def kda(lead):
        kh, vh, kd, taps = spec.kda_heads, spec.kda_value_heads, spec.kda_dim, spec.conv_kernel
        wide, v_wide = kh * kd, vh * kd

        def uniform(lo, hi, *shape):
            return jax.random.uniform(next(keys), lead + shape, jnp.float32, lo, hi)

        def gate(name, out):  # a rank-``kd`` pair, or one full product
            if spec.gates_low_rank:
                return {name + "_a": normal(*lead, d, kd), name + "_b": normal(*lead, kd, out)}
            return {name: normal(*lead, d, out)}

        # assumed (config.json gives none of them): the convolutions as
        # torch's Conv1d starts them; exp(A_log) uniform over 1..16; a step
        # softplus(dt_bias) log-uniform over 0.001..0.1
        decays = vh if spec.decay_per_head else wide
        step = jnp.exp(uniform(math.log(1e-3), math.log(1e-1), decays))
        return {
            "norm1": norm(*lead, d),
            "wq": normal(*lead, d, wide), "wk": normal(*lead, d, wide),
            "wv": normal(*lead, d, v_wide),
            **{c: uniform(-taps ** -0.5, taps ** -0.5, width, taps)
               for c, width in (("cq", wide), ("ck", wide), ("cv", v_wide))},
            **gate("wf", decays),
            "a_log": jnp.log(uniform(1.0, 16.0, vh)),
            "dt_bias": step + jnp.log(-jnp.expm1(-step)),
            "wb": normal(*lead, d, vh),
            **gate("wz", v_wide),
            "o_norm": jnp.ones(lead + (kd,), jnp.float32),
            "wo": normal(*lead, v_wide, d),
            "norm2": norm(*lead, d),
        }

    def sconv(lead):
        taps = spec.conv_kernel
        return {
            "norm1": norm(*lead, d),
            "win": normal(*lead, d, 3 * d),  # [B | C | x], in this order
            # assumed: the taps as torch's Conv1d starts them (one input channel a group)
            "conv": jax.random.uniform(next(keys), lead + (d, taps), jnp.float32,
                                       -taps ** -0.5, taps ** -0.5),
            "wout": normal(*lead, d, d),
            "norm2": norm(*lead, d),
        }

    def glu(lead, width, names):
        return {name: normal(*lead, *shape)
                for name, shape in zip(names, ((d, width), (d, width), (width, d)))}

    mixer = {"mla": mla, "gqa": gqa, "kda": kda, "sconv": sconv}
    params = {"embed": normal(spec.vocab, d)}
    for i in range(spec.dense_layers):
        params[dense_key(i)] = {**mixer[spec.mixers[i]](()), **glu((), spec.ffn, ("wg", "wu", "wd"))}
    for name, kind, _, count in spec.runs:
        n = (count,)
        params[name] = {
            **mixer[kind](n),
            "router": normal(*n, d, spec.routed),
            **glu(n + (spec.held,), spec.expert_width, ("eg", "eu", "ed")),
            **(glu(n, spec.shared_width, ("sg", "su", "sd")) if spec.shared_width else {}),
            **({"sgate": normal(*n, d, 1)} if spec.shared_gate else {}),
        }
    params["norm"] = norm(d)
    params["head"] = normal(d, spec.vocab)
    return params


# ---- the forward pass

def _norm(spec: SeqSpec):
    """The stack's RMS norm, looked up when the step is traced."""
    return nnseq.centred_rms_norm if spec.centred_norms else nnseq.rms_norm


def attention(lp, x, spec: SeqSpec, cast, mid):
    """``x [T, hidden]`` (``batch`` sequences of ``length``) plus its latent
    attention; the dims all heads share are turned by position where
    ``spec.rotary``, else they enter the score as they are."""
    b, s, h = spec.batch, spec.length, spec.heads
    pos = jnp.arange(s, dtype=jnp.int32)

    def turned(t):
        return nnseq.rotary(t, pos, spec.theta) if spec.rotary else t

    with jax.named_scope("seq/mla/project"):
        hn = nnseq.rms_norm(x, lp["norm1"], spec.eps)
        q = nnseq.matmul(hn, lp["wq"], cast).reshape(b, s, h, spec.nope + spec.rope)
        q = jnp.swapaxes(q, 1, 2)  # [B, H, S, nope + rope]
        q = jnp.concatenate(
            [q[..., : spec.nope], turned(q[..., spec.nope:])], axis=-1
        ).astype(mid).reshape(b * h, s, -1)
        ckr = nnseq.matmul(hn, lp["wkv_a"], cast)
        c = nnseq.rms_norm(ckr[:, : spec.kv_rank], lp["kv_norm"], spec.eps)
        k_rope = turned(ckr[:, spec.kv_rank:].reshape(b, s, spec.rope))
        kv = nnseq.matmul(c, lp["wkv_b"], cast, mid).reshape(b, s, h, spec.nope + spec.v_head)
        kv = jnp.swapaxes(kv, 1, 2)
        # the one key all heads share, laid beside each head's own
        k_rope = jnp.broadcast_to(k_rope[:, None].astype(mid), (b, h, s, spec.rope))
        k = jnp.concatenate([kv[..., : spec.nope], k_rope], axis=-1).reshape(b * h, s, -1)
        v = kv[..., spec.nope:].reshape(b * h, s, spec.v_head)
    with jax.named_scope("seq/mla/attend"):
        out = causal_edge_attention(q, k, v, 1.0 / math.sqrt(spec.nope + spec.rope), spec.block)
    with jax.named_scope("seq/mla/project"):
        out = jnp.swapaxes(out.reshape(b, h, s, spec.v_head), 1, 2).reshape(b * s, -1)
        return x + nnseq.matmul(out, lp["wo"], cast)


def gated_attention(lp, x, spec: SeqSpec, cast, mid):
    """``x [T, hidden]`` plus its grouped-query softmax attention, with an
    output gate where ``spec.attn_gate``: ``heads`` query heads of
    ``v_head`` dims share ``kv_heads`` key/value heads (query head ``m``
    attends head ``m // (heads / kv_heads)``); queries and keys through a
    norm per head (one weight of ``v_head`` for all heads) and rotary over
    their leading ``rope`` dims (all of them where ``rope == v_head``); the
    attention's output times ``sigmoid(gate)``, the gate being the second
    half of each head's query projection."""
    b, s, h, kv, head = spec.batch, spec.length, spec.heads, spec.kv_heads, spec.v_head
    pos = jnp.arange(s, dtype=jnp.int32)
    norm = _norm(spec)

    def by_head(t, weight):  # [T, heads * head] -> normed, turned, [B * heads, S, head]
        t = jnp.swapaxes(norm(t.reshape(b, s, -1, head), weight, spec.eps), 1, 2)
        return nnseq.rotary_leading(t, pos, spec.theta, spec.rope).astype(mid).reshape(-1, s, head)

    with jax.named_scope("seq/gqa/project"):
        hn = norm(x, lp["norm1"], spec.eps)
        qg = nnseq.matmul(hn, lp["wq"], cast).reshape(b * s, h, -1)
        if spec.attn_gate:
            gate = qg[..., head:].reshape(b * s, h * head)
        q = by_head(qg[..., :head], lp["q_norm"])
        k = by_head(nnseq.matmul(hn, lp["wk"], cast), lp["k_norm"])
        v = jnp.swapaxes(nnseq.matmul(hn, lp["wv"], cast, mid).reshape(b, s, kv, head), 1, 2)
    with jax.named_scope("seq/gqa/attend"):
        out = causal_edge_attention(q, k, v.reshape(b * kv, s, head), 1.0 / math.sqrt(head),
                                    spec.block, h // kv)
    with jax.named_scope("seq/gqa/project"):
        out = jnp.swapaxes(out.reshape(b, h, s, head), 1, 2).reshape(b * s, -1)
        if spec.attn_gate:
            out = nnseq.sigmoid_gate(out, gate)
        return x + nnseq.matmul(out, lp["wo"], cast)


def attention_tiles(spec: SeqSpec):
    """(key heads, group, positions, block, dk, dv) of the stack's softmax
    attention as ops/causal_attention.py tiles it; None where no layer
    attends that way."""
    block = causal_attention.resolve_block(spec.length, spec.block)
    if "gqa" in spec.mixers:
        return (spec.batch * spec.kv_heads, spec.heads // spec.kv_heads, spec.length, block, spec.v_head,
                spec.v_head)
    if "mla" in spec.mixers:
        return spec.batch * spec.heads, 1, spec.length, block, spec.nope + spec.rope, spec.v_head
    return None


OUT_GATES = {"sigmoid": {}, "silu": {"activation": jax.nn.silu}}  # gated_rms_norm's default is sigmoid


def delta_attention(lp, x, spec: SeqSpec, cast, mid):
    """``x [T, hidden]`` plus its gated delta-rule linear attention, one
    function for both gates (KDA; the per-head-gated delta rule): queries,
    keys and values through a short causal convolution and SiLU, queries
    and keys of unit length per head, a log-decay (per value head and key
    channel, or per value head: ``spec.decay_per_head``) and a write
    strength per value head from the same normed stream, the recurrence of
    ops/delta_rule.py (``kda_value_heads / kda_heads`` value heads share a
    key head's q and k), an RMS norm per head gated through a sigmoid or
    SiLU (``spec.out_gate``). The decay and the output gate come from
    rank-``kda_dim`` pairs or from one full product each
    (``spec.gates_low_rank``).

    Each operand's path from the normed stream (the product, then
    ops/conv_operand.py's one pass: convolution, SiLU, norm and the change
    of layout, float32 in VMEM and nowhere else; the decay's products,
    softplus and cumulative sum) and the output's path are recomputed in
    the backward, each under its own ``jax.checkpoint`` inside the layer's:
    what a delta-rule layer's backward holds at once is the operands and
    one product in the compute dtype and the gates' and the output's
    float32 intermediates (``[tokens, 4096]`` float32 is 0.5 GB at 32,768
    tokens), one path's at a time."""
    b, s, d = spec.batch, spec.length, spec.kda_dim
    norm = _norm(spec)

    def by_head(t):  # [B, S, H, ...] -> [B * H, S, ...]
        return jnp.swapaxes(t, 1, 2).reshape(b * t.shape[2], s, *t.shape[3:])

    def chain(hn, weights):  # hn W, or (hn Wa) Wb: the last product held in float32
        for w in weights[:-1]:
            hn = nnseq.matmul(hn, w, cast, mid)
        return nnseq.matmul(hn, weights[-1], cast)

    def gate_weights(name):
        return (lp[name + "_a"], lp[name + "_b"]) if spec.gates_low_rank else (lp[name],)

    @functools.partial(jax.checkpoint, static_argnums=3)
    def operand(hn, w, taps, scale):
        """One of q, k, v ``[B * H, S, d]``: ``scale`` None leaves it as
        the SiLU gives it, else unit length per head times ``scale``."""
        with jax.named_scope("seq/kda/project"):
            t = nnseq.matmul(hn, w, cast, mid)
        with jax.named_scope("seq/kda/conv"):
            return conv_operand(t.reshape(b, s, -1), taps, scale, t.shape[-1] // d)

    @jax.checkpoint
    def gates(hn, wf, a_log, dt_bias, wb):
        with jax.named_scope("seq/kda/project"):
            decay = chain(hn, wf)
            write = nnseq.matmul(hn, wb, cast)
        with jax.named_scope("seq/kda/gate"):
            # [B, S, value heads, key channels], or [B, S, value heads, 1]: a decay per head
            g = -jnp.exp(a_log)[:, None] * jax.nn.softplus(
                (decay + dt_bias).reshape(b, s, spec.kda_value_heads, -1))
            return (delta_rule.chunk_log_decay(by_head(g), spec.kda_chunk),
                    by_head(jax.nn.sigmoid(write).reshape(b, s, spec.kda_value_heads)))

    @jax.checkpoint
    def output(x, hn, out, wz, o_norm, wo):
        with jax.named_scope("seq/kda/project"):
            gate = chain(hn, wz)
        with jax.named_scope("seq/kda/out"):
            out = jnp.swapaxes(out.reshape(b, -1, s, d), 1, 2)
            out = nnseq.gated_rms_norm(out, o_norm, gate.reshape(out.shape), spec.eps,
                                       **OUT_GATES[spec.out_gate])
        with jax.named_scope("seq/kda/project"):
            return x + nnseq.matmul(out.reshape(b * s, -1), wo, cast)

    with jax.named_scope("seq/kda/project"):
        hn = norm(x, lp["norm1"], spec.eps)
    q = operand(hn, lp["wq"], lp["cq"], d ** -0.5)
    k = operand(hn, lp["wk"], lp["ck"], 1.0)
    v = operand(hn, lp["wv"], lp["cv"], None)
    log_decay, beta = gates(hn, gate_weights("wf"), lp["a_log"], lp["dt_bias"], lp["wb"])
    with jax.named_scope("seq/kda/recur"):
        out = delta_rule.chunked_delta_rule(q, k, v, log_decay, beta, cast)
    return output(x, hn, out, gate_weights("wz"), lp["o_norm"], lp["wo"])


def short_conv(lp, x, spec: SeqSpec, cast, mid):
    """``x [T, hidden]`` plus its gated short convolution: ``[B | C | x~] =
    h W_in`` (``h`` the normed stream, three widths of ``hidden``, held in
    the compute dtype), ``v = conv(B * x~)`` (depthwise, causal, ``conv_kernel``
    taps a channel, zeros before a sequence's start: nn/seq.py's
    ``causal_conv``), ``(C * v) W_out``. The gates and the convolution are
    float32 (``seq/sconv/conv``); the two products read the compute
    dtype."""
    b, s, d = spec.batch, spec.length, spec.hidden
    with jax.named_scope("seq/sconv/project"):
        hn = _norm(spec)(x, lp["norm1"], spec.eps)
        bcx = nnseq.matmul(hn, lp["win"], cast, mid).reshape(b, s, 3 * d)
    with jax.named_scope("seq/sconv/conv"):
        gate_b, gate_c, xt = (bcx[..., i * d: (i + 1) * d].astype(jnp.float32) for i in range(3))
        v = nnseq.causal_conv(gate_b * xt, lp["conv"])
        y = (gate_c * v).astype(mid).reshape(b * s, d)
    with jax.named_scope("seq/sconv/out"):
        return x + nnseq.matmul(y, lp["wout"], cast)


MIXERS = {"mla": attention, "gqa": gated_attention, "kda": delta_attention, "sconv": short_conv}


def dense_layer(lp, x, spec: SeqSpec, cast, mid, kind: str):
    x = MIXERS[kind](lp, x, spec, cast, mid)
    with jax.named_scope("seq/dense_mlp"):
        hn = nnseq.rms_norm(x, lp["norm2"], spec.eps)
        return x + nnseq.swiglu(hn, lp["wg"], lp["wu"], lp["wd"], cast)


ROUTER_SCORES = {"sigmoid": jax.nn.sigmoid, "softmax": functools.partial(jax.nn.softmax, axis=-1)}


def expert_mlp(lp, bias, x, spec: SeqSpec, cast):
    """(``x`` plus this chip's part of the expert layer, rows per held
    expert [held], the choice of experts [T, k]). The router's scores are a
    sigmoid or a softmax over all the layer's experts (``spec.scoring``),
    the chosen ones' renormalised either way (``moe.route``); the shared
    expert is added as it is or behind its own sigmoid gate
    (``spec.shared_gate``), and not at all where ``spec.shared_width`` is
    0."""
    with jax.named_scope("seq/moe/route"):
        hn = _norm(spec)(x, lp["norm2"], spec.eps)
        # float32 at the highest precision, as the published code keeps it
        scores = ROUTER_SCORES[spec.scoring](nnseq.matmul(hn, lp["router"], lambda t: t))
        # eps by keyword only where it is not moe.route's own: the fault driver of the accepted
        # cells (benchmark/tests/seq_fault_driver.py) stands in for moe.route with four arguments
        own = {} if spec.route_eps == moe.ROUTE_EPS else {"eps": spec.route_eps}
        choice, weight = moe.route(scores, bias, spec.per_token, spec.route_scale, **own)
    with jax.named_scope("seq/moe/dispatch"):
        plan = moe.plan_dispatch(choice, spec.first, spec.held, spec.moe_chunk)
    # dispatch, experts and combine are one walk of the list's routed rows,
    # each part of it under its own scope (moe.combine_rows)
    rows = moe.ExpertRows(cast(hn), cast(lp["eg"]), cast(lp["eu"]), cast(lp["ed"]))
    routed = moe.combine_rows(rows, weight, plan)
    if not spec.shared_width:
        return x + routed, plan.group_sizes, choice
    with jax.named_scope("seq/moe/shared"):
        out = x + routed
        shared = nnseq.swiglu(hn, lp["sg"], lp["su"], lp["sd"], cast)
        if spec.shared_gate:
            shared = jax.nn.sigmoid(nnseq.matmul(hn, lp["sgate"], cast)) * shared
        out = out + shared
    return out, plan.group_sizes, choice


def _embed(p, _, tokens):
    with jax.named_scope("seq/embed"):
        return p["embed"][tokens.reshape(-1)], None


def _dense(p, x, spec: SeqSpec, cast, mid, i: int):
    kind = spec.mixers[i]
    return jax.checkpoint(lambda lp, x: dense_layer(lp, x, spec, cast, mid, kind))(p[dense_key(i)], x), None


def _run(p, x, spec: SeqSpec, cast, mid, run_bias, name: str, kind: str):
    mixer = MIXERS[kind]

    @jax.checkpoint
    def layer(x, lp, b):
        x = mixer(lp, x, spec, cast, mid)
        return expert_mlp(lp, b, x, spec, cast)

    def body(x, lp_b):
        x, size, choice = layer(x, *lp_b)
        return x, (size, choice)

    return lax.scan(body, x, (p[name], run_bias))


def stack_parts(bias, tokens, spec: SeqSpec, cast, mid):
    """The stack below the head as its parts, in order: embed, each leading
    dense layer, each run of expert layers. A part is (the parameter tree's
    top-level keys it reads, a function of those parameters and the
    residual stream it is handed, giving the stream it hands on and, for a
    run, (rows per held expert, choices), else None)."""
    parts = [(("embed",), functools.partial(_embed, tokens=tokens))]
    for i in range(spec.dense_layers):
        parts.append(((dense_key(i),), functools.partial(_dense, spec=spec, cast=cast, mid=mid, i=i)))
    for name, kind, start, count in spec.runs:
        # the run's rows of the bias; a run of all the expert layers takes it whole
        run_bias = bias if count == spec.moe_layers else bias[start: start + count]
        parts.append(((name,), functools.partial(_run, spec=spec, cast=cast, mid=mid, run_bias=run_bias,
                                                 name=name, kind=kind)))
    return parts


ROUTED = ("eg", "eu", "ed")  # a run's routed experts' leaves


def _take(tree, keys) -> Dict[str, Any]:
    return {k: tree[k] for k in keys}


def _routed(found):
    """(rows per held expert [L, held], the choices [L, T, k]) of the runs'
    (sizes, choices)."""
    sizes, choices = zip(*found)
    if len(sizes) == 1:
        return sizes[0], choices[0]
    return jnp.concatenate(sizes), jnp.concatenate(choices)


def hidden_states(params, bias, tokens, spec: SeqSpec, cast, mid):
    """(the residual stream [T, hidden] after the last layer, rows per held
    expert [L, held], the choices [L, T, k]) of ``tokens`` [batch, length]."""
    x, found = None, []
    for keys, part in stack_parts(bias, tokens, spec, cast, mid):
        x, routed = part(_take(params, keys), x)
        if routed is not None:
            found.append(routed)
    return (x, *_routed(found))


def head_loss(params, x, tokens, spec: SeqSpec, cast):
    """Mean next-token cross-entropy; the head and the loss in chunks of
    ``loss_chunk`` tokens, each recomputed in the backward."""
    with jax.named_scope("seq/head_loss"):
        targets = jnp.roll(tokens, -1, axis=1).reshape(-1)
        weight = jnp.broadcast_to(
            (jnp.arange(spec.length) < spec.length - 1).astype(jnp.float32), tokens.shape
        ).reshape(-1)
        hn = _norm(spec)(x, params["norm"], spec.eps)
        n = spec.tokens // spec.loss_chunk

        @jax.checkpoint
        def chunk(total, part):
            h_c, t_c, w_c = part
            logits = nnseq.matmul(h_c, params["head"], cast)
            picked = jnp.take_along_axis(logits, t_c[:, None], axis=-1)[:, 0]
            return total + jnp.sum((jax.nn.logsumexp(logits, axis=-1) - picked) * w_c), None

        total, _ = lax.scan(chunk, jnp.zeros((), jnp.float32), (
            hn.reshape(n, spec.loss_chunk, -1), targets.reshape(n, -1), weight.reshape(n, -1)))
        return total / weight.sum()


def scope_of(op_name: str) -> Optional[str]:
    """The innermost of ``SCOPES`` an HLO ``op_name`` lies under."""
    found = [(op_name.rfind(s), s) for s in SCOPES if s in op_name]
    return max(found)[1] if found else None


_HLO_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = [^\n]*?\b[\w\-]+\(([^\n]*)$", re.M)
_HLO_OP_NAME = re.compile(r"op_name=\"([^\"]*)\"")
_HLO_OPERAND = re.compile(r"%([\w.\-]+)")


def scope_table_of(hlo_text: str, rounds: int = 4) -> Dict[str, str]:
    """Instruction name -> scope, for the instructions of an HLO module's
    text: the innermost of ``SCOPES`` its ``op_name`` lies under. An
    instruction the compiler made without the scope (the TPU's rewrite of
    ``ragged_dot`` into a custom call named ``ragged-dot-none``, a copy, a
    get-tuple-element, a bitcast) takes the scope most of the instructions
    that read it have, else of those it reads, over a few rounds."""
    table: Dict[str, str] = {}
    operands: Dict[str, list] = {}
    for name, rest in _HLO_INSTRUCTION.findall(hlo_text):
        op_name = _HLO_OP_NAME.search(rest)
        scope = scope_of(op_name.group(1)) if op_name else None
        if scope is not None:
            table[name] = scope
        # operands stand before the closing parenthesis of the call
        operands[name] = _HLO_OPERAND.findall(rest.split("), ")[0])
    users: Dict[str, list] = {}
    for name, ops in operands.items():
        for op in ops:
            users.setdefault(op, []).append(name)
    for _ in range(rounds):
        found = {}
        for name in operands:
            if name in table:
                continue
            for near in (users.get(name, ()), operands[name]):
                scopes = [table[n] for n in near if n in table]
                if scopes:
                    found[name] = max(set(scopes), key=scopes.count)
                    break
        if not found:
            break
        table.update(found)
    return table


@register_algorithm("SEQLM")
class SeqLMTrainer(ToolkitBase):
    needs_device_graph = False

    # ---- the funnel ------------------------------------------------------
    def init_graph(self) -> None:
        """Nothing to load: the graph is implicit (every position sees the
        positions before it in its sequence)."""
        log.info("implicit causal graph: no edge table is read or built")

    def init_nn(self) -> None:
        cfg = self.cfg
        spec = self._read_spec()
        with self.timers.phase("datum_load"):
            if cfg.token_file:
                self.datum = TokenDatum.read(
                    cfg.resolve_path(cfg.token_file, self.base_dir), spec.length, spec.vocab)
            else:
                self.datum = TokenDatum.random_generate(
                    cfg.seq_corpus * spec.batch, spec.length, spec.vocab, seed=self.seed)
        self._finalize_datum()

    @classmethod
    def from_tokens(cls, cfg: InputInfo, tokens: np.ndarray, seed: int = 0,
                    base_dir: Optional[str] = None) -> "SeqLMTrainer":
        """Construct from in-memory token ids [sequences, length] (tests,
        the benchmark): the funnel from ``_finalize_datum`` on, as
        ``from_arrays`` enters it for the vertex families."""
        t = cls(cfg, base_dir=base_dir, seed=seed)
        t.datum = TokenDatum(tokens, t._read_spec().vocab)
        t._finalize_datum()
        return t

    def _read_spec(self) -> SeqSpec:
        if not self.cfg.model_file:
            raise ValueError("ALGORITHM:SEQLM needs MODEL_FILE: the model's config.json")
        with open(self.cfg.resolve_path(self.cfg.model_file, self.base_dir)) as fh:
            self.model = json.load(fh)
        self.spec = SeqSpec.from_cfg(self.model, self.cfg)
        return self.spec

    def build_model(self) -> None:
        cfg, spec = self.cfg, self.spec
        if self.datum.length != spec.length or self.datum.sequences % spec.batch:
            raise ValueError(
                f"the corpus [{self.datum.sequences}, {self.datum.length}] does not cut into "
                f"batches of SEQ_BATCH:{spec.batch} sequences of SEQ_LENGTH:{spec.length}"
            )
        self.compute_dtype = jnp.bfloat16 if cfg.precision == "bfloat16" else None
        self.adam_cfg = AdamConfig(
            alpha=cfg.learn_rate, weight_decay=cfg.weight_decay,
            decay_rate=cfg.decay_rate, decay_epoch=cfg.decay_epoch,
            warmup_steps=cfg.warmup_epochs,
        )
        with self.timers.phase("params_init"):
            self.params, self.opt_state = self.initial_state()
            # the noaux_tc correction bias: a buffer, no gradient; fixed at
            # zero (config.json gives no update rate for it)
            self.route_bias = jnp.zeros((spec.moe_layers, spec.routed), jnp.float32)
        with self.timers.phase("datum_upload"):
            self.n_batches = self.datum.sequences // spec.batch
            self.corpus = jnp.asarray(
                self.datum.tokens.reshape(self.n_batches, spec.batch, spec.length))
            self._batch_index = [jnp.asarray(i, jnp.int32) for i in range(self.n_batches)]
        with self.timers.phase("step_build"):
            self._train_step = jax.jit(self._step, donate_argnums=(0, 1))
            self._eval_logits = jax.jit(self._logits_at)
            self._scope_table: Optional[Dict[str, str]] = None
        self.routed_history: list = []  # pairs sent to held experts, per epoch
        n_params = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(self.params))
        for kind in MIXERS:
            self.metrics.gauge_set(f"seq.{kind}_layers", spec.mixers.count(kind))
        self.metrics.gauge_set("kda.chunk", spec.kda_chunk)
        self.metrics.gauge_set("kda.key_heads", spec.kda_heads)
        self.metrics.gauge_set("kda.value_heads", spec.kda_value_heads)
        self.metrics.gauge_set("kda.decay_per_head", int(spec.decay_per_head))
        # delta-rule layers whose chunk walk lowers to ops/delta_rule.py's kernels, over all of
        # them (one shape a stack: all or none), and the rows of v a grid step of theirs holds
        rows = spec.batch * spec.kda_value_heads
        fused = "kda" in spec.mixers and jax.default_backend() == "tpu" and delta_rule.kernel_takes(
            spec.batch * spec.kda_heads, rows, spec.length, spec.kda_dim, spec.kda_dim, spec.kda_chunk)
        self.metrics.gauge_set("kda.recur_fused", float(fused))
        self.metrics.gauge_set("kda.rows_per_block", delta_rule.rows_per_block(rows) if fused else 0)
        # whether the stack's softmax attention lowers to ops/causal_attention.py's kernels, and
        # the query rows a grid step of theirs holds
        tiles = attention_tiles(spec)
        on_kernel = tiles is not None and jax.default_backend() == "tpu" and causal_attention.kernel_takes(
            *tiles[2:])
        self.metrics.gauge_set("attn.kernel", float(on_kernel))
        self.metrics.gauge_set("attn.rows_per_tile", causal_attention.rows_per_tile(*tiles) if on_kernel else 0)
        log.info(
            "SEQLM: %d layers (%d dense + %d expert; mixers %s), experts %d..%d of %d held, "
            "vocabulary slice %d, %d parameters; a step is %d sequences of %d tokens; corpus of "
            "%d batches",
            len(spec.mixers), spec.dense_layers, spec.moe_layers, "-".join(spec.mixers), spec.first,
            spec.first + spec.held - 1, spec.routed, spec.vocab, n_params, spec.batch,
            spec.length, self.n_batches,
        )

    def initial_state(self):
        """(params, opt_state) as ``build_model`` makes them from the seed:
        a second call gives the same bits (a check replays the first steps
        from here)."""
        params = jax.jit(init_params, static_argnums=1)(jax.random.PRNGKey(self.seed), self.spec)
        return params, adam_init(params)

    # ---- the step --------------------------------------------------------
    def _casts(self):
        cast = compute_cast(self.compute_dtype)
        return cast, (self.compute_dtype or jnp.float32)

    def _loss(self, params, bias, tokens):
        cast, mid = self._casts()
        x, sizes, choice = hidden_states(params, bias, tokens, self.spec, cast, mid)
        return head_loss(params, x, tokens, self.spec, cast), (sizes, choice)

    def _step(self, params, opt_state, bias, corpus, index):
        """The loss, its gradients and Adam, part by part: the backward
        takes the head and the stack's parts (``stack_parts``) from the top
        down, and each part's parameters take their update from the
        gradients its backward made. A run's routed experts are updated
        before the next part's backward begins: an optimization barrier
        holds that backward until their new values, moments included, are
        made. Left to itself the compiler may defer a run's expert update
        to the step's end and keep its float32 gradients (384 MiB in the
        Qwen3-Next cell's attention layer) through the rest of the backward
        (PERF.md section 6). The other parts' updates are left where the
        compiler puts them: held the same way, they raised the peak of the
        Kimi-Linear cell's step by 281 MB on a v5e."""
        tokens = corpus[index]
        cast, mid = self._casts()
        spec = self.spec
        parts = stack_parts(bias, tokens, spec, cast, mid) + [
            (("norm", "head"), lambda p, x: (head_loss(p, x, tokens, spec, cast), None))]
        x, pulls, found = None, [], []
        for keys, part in parts:
            x, pull, routed = jax.vjp(part, _take(params, keys), x, has_aux=True)
            pulls.append(pull)
            if routed is not None:
                found.append(routed)
        loss, (sizes, choice) = x, _routed(found)
        runs = {name for name, *_ in spec.runs}
        new_params, new_m, new_v = dict(params), dict(opt_state.m), dict(opt_state.v)
        g = jnp.ones_like(loss)
        for (keys, _), pull in zip(parts[::-1], pulls[::-1]):
            grads, g = pull(g)
            with jax.named_scope("seq/adam"):
                own, state = adam_update(_take(params, keys), grads, AdamState(
                    _take(opt_state.m, keys), _take(opt_state.v, keys), opt_state.step), self.adam_cfg)
            if keys[0] in runs:
                (name,) = keys
                trees = (own, state.m, state.v)
                g, *pinned = lax.optimization_barrier((g, *(_take(t[name], ROUTED) for t in trees)))
                own, m, v = ({name: dict(t[name], **new)} for t, new in zip(trees, pinned))
                state = AdamState(m, v, state.step)
            new_params.update(own)
            new_m.update(state.m)
            new_v.update(state.v)
        # the step's own choice of experts [L, T, k] stays on the device: a
        # check that follows the step reads it, the run loop does not
        return new_params, AdamState(new_m, new_v, state.step), loss, sizes, choice

    def _logits_at(self, params, bias, tokens, rows):
        """(logits [len(rows), vocab] float32 at the flat positions
        ``rows`` of ``tokens`` [batch, length], the choices [L, T, k])."""
        cast, mid = self._casts()
        x, _, choice = hidden_states(params, bias, tokens, self.spec, cast, mid)
        hn = _norm(self.spec)(x[rows], params["norm"], self.spec.eps)
        return nnseq.matmul(hn, params["head"], cast), choice

    def step_args(self, index: int = 0):
        """The argument tuple ``run()`` passes to the jitted step."""
        return (self.params, self.opt_state, self.route_bias, self.corpus,
                self._batch_index[index % self.n_batches])

    aot_args = step_args

    def scope_table(self) -> Dict[str, str]:
        """Instruction name -> scope of the compiled step (lowered once
        more from the same arguments: with the compile cache a lookup).
        That program's size, where the backend analyses it, goes to the
        gauge ``step.generated_code_bytes``."""
        if self._scope_table is None:
            compiled = self._train_step.lower(*self.step_args()).compile()
            self._scope_table = scope_table_of(compiled.as_text())
            memory = compiled.memory_analysis()
            if memory is not None:
                self.metrics.gauge_set(
                    "step.generated_code_bytes", int(memory.generated_code_size_in_bytes))
        return self._scope_table

    # ---- run -------------------------------------------------------------
    def run(self) -> Dict[str, Any]:
        cfg, spec = self.cfg, self.spec
        self.open_run_root()
        with self.stage("run_begin"):
            log.info(
                "GNNmini::Engine[%s.%s] running [%d] Epochs (one optimizer step each)",
                jax.default_backend(), type(self).__name__, cfg.epochs,
            )
        with self.stage("ckpt_begin"):
            start_epoch = self.ckpt_begin()
        # epochs count on from the last run() on this trainer (a warm-up
        # run() then a measured one): the corpus keeps cycling
        first = start_epoch or len(self.loss_history)
        loss = None
        for epoch in range(first, first + max(cfg.epochs - start_epoch, 0)):
            with self.epoch_span(epoch):
                with self.stage("epoch_key", epoch):
                    index = self._batch_index[epoch % self.n_batches]
                with self.stage("step_dispatch", epoch) as s_disp:
                    self.params, self.opt_state, loss, sizes, _ = self._train_step(
                        self.params, self.opt_state, self.route_bias, self.corpus, index)
                with self.stage("step_device", epoch) as s_dev:
                    jax.block_until_ready(loss)
                with self.stage("loss_fetch", epoch):
                    loss = fault_point("epoch_loss", epoch=epoch, value=loss)
                    sizes = np.asarray(sizes)
                    dt = get_time() - s_disp.t0
                    self.epoch_times.append(dt)
                    self.loss_history.append(float(loss))
                    self._count_epoch(sizes)
                with self.stage("epoch_emit", epoch):
                    self.emit_epoch(epoch, dt, loss, stages={
                        "step_dispatch": s_disp.dur_s, "step_device": s_dev.dur_s})
                if epoch % max(1, cfg.epochs // 20) == 0:
                    log.info("Epoch %d loss %f", epoch, float(loss))
                with self.stage("ckpt_epoch_end", epoch):
                    self.ckpt_epoch_end(epoch)
        with self.stage("ckpt_final"):
            self.ckpt_final()
        avg = self.avg_epoch_time()
        log.info("--avg epoch time %.4f s (first %.2f s incl. compile)",
                 avg, self.epoch_times[0] if self.epoch_times else 0.0)
        result = {
            "loss": float(loss) if loss is not None else float("nan"),
            "acc": {"train": None, "eval": None, "test": None},
            "avg_epoch_s": avg,
        }
        self.finalize_metrics(result)
        return result

    def _count_epoch(self, sizes: np.ndarray) -> None:
        """``sizes`` [L, held]: rows each held expert of each layer saw."""
        rows = int(sizes.sum())
        self.routed_history.append(rows)
        self.metrics.counter_add("seq.tokens", self.spec.tokens)
        self.metrics.counter_add("moe.rows_routed", rows)
        self.metrics.counter_add("kda.token_layers", self.spec.tokens * self.spec.kda_layers)
        self.metrics.counter_add("gqa.token_layers", self.spec.tokens * self.spec.mixers.count("gqa"))
        self.metrics.counter_add("sconv.token_layers",
                                 self.spec.tokens * self.spec.mixers.count("sconv"))
        # how far the walk of the sorted lists went (moe.combine_rows), of how far it could
        list_rows = self.spec.tokens * self.spec.per_token
        self.metrics.gauge_set("moe.rows_walked", sum(
            moe.rows_walked(int(n), list_rows, self.spec.moe_chunk) for n in sizes.sum(axis=1)))
        self.metrics.gauge_set("moe.list_rows", sizes.shape[0] * list_rows)
        mean = np.maximum(sizes.mean(axis=1), 1e-9)
        self.metrics.gauge_set("moe.load_max_over_mean", float((sizes.max(axis=1) / mean).max()))
