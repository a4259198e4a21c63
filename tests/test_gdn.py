"""The third dialect of the token-sequence family (a ``qwen3_next`` file):
the delta rule with a decay per head and more value heads than key heads
(ops/delta_rule.py), grouped-query attention with an output gate
(ops/causal_attention.py's group axis), zero-centred and SiLU-gated norms
and partial rotary (nn/seq.py), a softmax router with a gated shared expert
and a stack without a dense layer (models/seqlm.py), against the plain
reference the benchmark compares with (benchmark/reference/qwen3_next.py,
loaded by its path: one reference, no second copy) or the formula a part
replaces, on the CPU, small widths, seeded weights."""

import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import test_kda
from test_kda import rel

from neutronstarlite_tpu.models import seqlm
from neutronstarlite_tpu.nn import seq as nnseq
from neutronstarlite_tpu.nn.layers import compute_cast
from neutronstarlite_tpu.ops import delta_rule
from neutronstarlite_tpu.ops.causal_attention import causal_edge_attention
from neutronstarlite_tpu.utils.config import InputInfo

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_reference():
    path = os.path.join(REPO, "benchmark", "reference", "qwen3_next.py")
    spec = importlib.util.spec_from_file_location("reference_qwen3_next", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _load_reference()

# the keys of a qwen3_next config.json, small: eight layers, every fourth attends
MODEL = dict(
    model_type="qwen3_next", hidden_size=48, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, partial_rotary_factor=0.25, rope_theta=10000000, rms_norm_eps=1e-6,
    linear_num_key_heads=2, linear_num_value_heads=4, linear_key_head_dim=8,
    linear_value_head_dim=8, linear_conv_kernel_dim=4, num_experts=32, num_experts_per_tok=3,
    moe_intermediate_size=32, shared_expert_intermediate_size=24, intermediate_size=96,
    norm_topk_prob=True, decoder_sparse_step=1, mlp_only_layers=[], full_attention_interval=4,
    hidden_act="silu", num_hidden_layers=8, vocab_size=128, max_position_embeddings=64,
    rope_scaling=None, use_sliding_window=False, tie_word_embeddings=False,
)
SHAPE = ref.Shape.of(MODEL)
CUT = dict(SEQ_LAYERS=4, SEQ_LENGTH=32, SEQ_BATCH=2, SEQ_CORPUS=3, EXPERT_SHARDS=4,
           EXPERT_SHARD=1, VOCAB_SHARDS=2, ATTN_BLOCK=8, LOSS_CHUNK=16, KDA_CHUNK=16, EPOCHS=2,
           LEARN_RATE=0.0003, WEIGHT_DECAY=0.0001, DECAY_EPOCH=-1)


def make_trainer(tmp_path, **keys):
    """test_kda's trainer from a cfg file beside its model JSON, over this
    file's model and cut."""
    return test_kda.make_trainer(tmp_path, model=MODEL, **dict(CUT, **keys))


def _bumped(params, rng):
    """``params`` with every norm weight moved off its start, so that ``1 +
    w`` is not ``1`` and ``w`` not ``1 + w``."""
    def bump(path, a):
        if "norm" in jax.tree_util.keystr(path):
            return a + rng.normal(0.0, 0.3, a.shape).astype(np.float32)
        return a
    return jax.tree_util.tree_map_with_path(bump, jax.tree.map(np.asarray, params))


# ---- the per-head decay through the chunk scan, against the recurrence position by position

def _recurrence_inputs(rng, key_rows, each, positions, dk, dv, gate):
    unit = lambda t: t / np.linalg.norm(t, axis=-1, keepdims=True)  # noqa: E731
    q = unit(rng.standard_normal((key_rows, positions, dk))).astype(np.float32)
    k = unit(rng.standard_normal((key_rows, positions, dk))).astype(np.float32)
    v = rng.standard_normal((key_rows * each, positions, dv)).astype(np.float32)
    g = -rng.uniform(0.0, gate, (key_rows * each, positions, 1)).astype(np.float32)
    beta = rng.uniform(0.0, 1.0, (key_rows * each, positions)).astype(np.float32)
    return tuple(jnp.asarray(t) for t in (q, k, v, g, beta))


def _by_tokens(q, k, v, g, beta):
    """The reference's recurrence over rows ``[N, S, .]`` (it takes ``[T, H,
    .]`` and one decay a head), key rows repeated for their value rows."""
    each = v.shape[0] // k.shape[0]
    t = lambda a: jnp.swapaxes(a, 0, 1)  # noqa: E731
    q, k = jnp.repeat(q, each, axis=0), jnp.repeat(k, each, axis=0)
    return t(ref.delta_rule_tokens(t(q), t(k), t(v), t(g[..., 0]), t(beta), segment=16))


@pytest.mark.parametrize("each", [1, 2])
@pytest.mark.parametrize("chunk", [64, 16])
@pytest.mark.parametrize("gate", [0.1, 60.0])
def test_the_per_head_chunk_path_is_the_recurrence(rng, gate, chunk, each):
    """Outputs and every gradient, float32, value heads 1x and 2x the key
    heads. At ``gate`` 60 a chunk's cumulative decay is ``exp(-2000)``: it
    underflows, a quotient of two of them would be 0/0, and the mask of
    ``exp(G_i - G_j)`` stays finite and equal."""
    args = _recurrence_inputs(rng, 2, each, 128, 8, 6, gate)
    weight = jnp.asarray(rng.standard_normal((2 * each, 128, 6)).astype(np.float32))
    with jax.default_matmul_precision("highest"):
        got = delta_rule.gated_delta_rule(*args, chunk=chunk)
        want = _by_tokens(*args)
        d_got = jax.grad(lambda *a: jnp.sum(delta_rule.gated_delta_rule(*a, chunk=chunk) * weight),
                         argnums=range(5))(*args)
        d_want = jax.grad(lambda *a: jnp.sum(_by_tokens(*a) * weight), argnums=range(5))(*args)
    assert np.all(np.isfinite(np.asarray(got))) and rel(got, want) < 1e-5
    for name, a, b in zip("q k v g beta".split(), d_got, d_want):
        assert a.shape == b.shape and np.all(np.isfinite(np.asarray(a))), name
        # the decay's gradient comes back through a chunk's cumulative sum: float32 sums of
        # entries 1e5 apart at the strong gate
        assert rel(a, b) < (2e-4 if name == "g" else 5e-5), name


@pytest.mark.parametrize("dtype, limit", [(jnp.float32, 1e-5), (jnp.bfloat16, 1e-2)])
@pytest.mark.parametrize("gate", [0.1, 3.0, 60.0])
@pytest.mark.parametrize("each", [1, 2])
def test_the_kernel_is_the_scan_with_a_decay_per_head(rng, each, gate, dtype, limit):
    """ops/delta_rule.py's kernels (interpret mode) against its scan: a
    key head beside its one or two value heads in a row block."""
    test_kda.the_kernel_is_the_scan(rng, True, each, gate, dtype, limit)


@pytest.mark.parametrize("chunk", [64, 16])
def test_the_per_head_chunk_path_in_bfloat16_stays_near_the_recurrence(rng, chunk):
    """The products read bfloat16 operands (the decay, the system and the
    state stay float32): outputs and gradients within bfloat16's grain."""
    args = _recurrence_inputs(rng, 2, 2, 128, 8, 6, 0.5)
    weight = jnp.asarray(rng.standard_normal((4, 128, 6)).astype(np.float32))
    cast = compute_cast(jnp.bfloat16)
    low = lambda *a: delta_rule.gated_delta_rule(*a, chunk=chunk, cast=cast)  # noqa: E731
    got, want = low(*args), _by_tokens(*args)
    d_got = jax.grad(lambda *a: jnp.sum(low(*a) * weight), argnums=range(5))(*args)
    d_want = jax.grad(lambda *a: jnp.sum(_by_tokens(*a) * weight), argnums=range(5))(*args)
    assert rel(got, want) < 2e-2
    for name, a, b in zip("q k v g beta".split(), d_got, d_want):
        assert rel(a, b) < 5e-2, name


@pytest.mark.parametrize("each", [1, 2])
def test_the_per_head_path_is_the_per_channel_path_given_one_decay_on_every_channel(rng, each):
    """The cheaper path and the sub-block path are one recurrence: a
    log-decay ``[N, S, 1]`` against the same numbers broadcast to every key
    channel, outputs and gradients (the channels' gradients summed)."""
    q, k, v, g, beta = _recurrence_inputs(rng, 3, each, 64, 8, 6, 2.0)
    wide = jnp.broadcast_to(g, g.shape[:2] + (8,))
    weight = jnp.asarray(rng.standard_normal(v.shape).astype(np.float32))
    run = lambda q, k, v, g, beta: jnp.sum(  # noqa: E731
        delta_rule.gated_delta_rule(q, k, v, g, beta, chunk=32) * weight)
    with jax.default_matmul_precision("highest"):
        assert rel(delta_rule.gated_delta_rule(q, k, v, g, beta, chunk=32),
                   delta_rule.gated_delta_rule(q, k, v, wide, beta, chunk=32)) < 1e-5
        per_head = jax.grad(run, argnums=range(5))(q, k, v, g, beta)
        per_channel = jax.grad(run, argnums=range(5))(q, k, v, wide, beta)
    for name, a, b in zip("q k v g beta".split(), per_head, per_channel):
        b = b.sum(axis=-1, keepdims=True) if name == "g" else b
        assert rel(a, b) < 5e-5, name


def test_rows_that_do_not_share_evenly_are_refused():
    z = jnp.zeros
    with pytest.raises(ValueError, match="do not serve a whole number"):
        delta_rule.gated_delta_rule(z((2, 16, 4)), z((2, 16, 4)), z((3, 16, 4)), z((3, 16, 1)),
                                    z((3, 16)), chunk=16)


# ---- grouped attention against repeated keys and values

def _plain_attention(q, k, v, scale):
    """Softmax attention over ``j <= i`` with every query row's own copy of
    its key/value head: what the group axis replaces."""
    each = q.shape[0] // k.shape[0]
    k, v = jnp.repeat(k, each, axis=0), jnp.repeat(v, each, axis=0)
    s = jnp.einsum("nqd,nkd->nqk", q, k, precision="highest") * scale
    mask = jnp.tril(jnp.ones(s.shape[-2:], bool))
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    return jnp.einsum("nqk,nkd->nqd", p, v, precision="highest")


@pytest.mark.parametrize("group", [1, 4, 8])
def test_grouped_attention_is_attention_over_repeated_keys_and_values(rng, group):
    """Forward, ``dq``, and ``dK``, ``dV`` summed over the group, three
    blocks of 8 positions."""
    n, s, dk, dv = 3, 24, 8, 6
    q = jnp.asarray(rng.standard_normal((n * group, s, dk)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((n, s, dk)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((n, s, dv)).astype(np.float32))
    weight = jnp.asarray(rng.standard_normal((n * group, s, dv)).astype(np.float32))
    got = causal_edge_attention(q, k, v, 0.3, 8, group)
    assert got.shape == (n * group, s, dv) and rel(got, _plain_attention(q, k, v, 0.3)) < 1e-5
    d_got = jax.grad(lambda *a: jnp.sum(causal_edge_attention(*a, 0.3, 8, group) * weight),
                     argnums=(0, 1, 2))(q, k, v)
    d_want = jax.grad(lambda *a: jnp.sum(_plain_attention(*a, 0.3) * weight), argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip(("dq", "dK", "dV"), d_got, d_want):
        assert a.shape == b.shape and rel(a, b) < 1e-5, name


def test_a_grouped_query_reads_no_later_position_no_other_group_and_no_other_sequence(rng):
    n, group, s, d = 2, 4, 16, 8
    q = rng.standard_normal((n * group, s, d)).astype(np.float32)
    k = rng.standard_normal((n, s, d)).astype(np.float32)
    v = rng.standard_normal((n, s, d)).astype(np.float32)
    run = lambda k, v: np.asarray(causal_edge_attention(  # noqa: E731
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), 0.3, 8, group))
    base = run(k, v)
    for at in (3, 7, 12):  # inside a block, a block's last position, the later block
        moved_k, moved_v = k.copy(), v.copy()
        moved_k[0, at] += 1.0
        moved_v[0, at] += 1.0
        moved = run(moved_k, moved_v)
        assert np.array_equal(moved[:group, :at], base[:group, :at])  # no later position
        for row in range(group):  # every head of the group reads the moved key
            assert not np.array_equal(moved[row, at:], base[row, at:])
        assert np.array_equal(moved[group:], base[group:])  # the other group, the other sequence
    # a key/value head's gradient comes from its own group's queries alone
    d_k = np.asarray(jax.grad(lambda k: jnp.sum(causal_edge_attention(
        jnp.asarray(q), k, jnp.asarray(v), 0.3, 8, group)[:group]))(jnp.asarray(k)))
    assert np.any(d_k[0]) and not np.any(d_k[1])
    with pytest.raises(ValueError, match="are not 4 rows for each"):
        causal_edge_attention(jnp.asarray(q[:6]), jnp.asarray(k), jnp.asarray(v), 0.3, 8, group)


# ---- nn/seq.py: partial rotary, the zero-centred and the gated norm

def test_rotary_over_the_leading_dims_leaves_the_rest(rng):
    x = rng.standard_normal((2, 3, 10, 16)).astype(np.float32)
    pos = jnp.arange(10, dtype=jnp.int32)
    got = np.asarray(nnseq.rotary_leading(jnp.asarray(x), pos, 1e7, 4))
    assert np.array_equal(got[..., 4:], x[..., 4:])
    assert rel(got[..., :4], nnseq.rotary(jnp.asarray(x[..., :4]), pos, 1e7)) == 0.0
    angle = np.arange(10)[:, None] * 1e7 ** (-np.arange(2) / 2.0)  # dim i pairs with i + 2
    want = np.concatenate([x[..., :2] * np.cos(angle) - x[..., 2:4] * np.sin(angle),
                           x[..., 2:4] * np.cos(angle) + x[..., :2] * np.sin(angle)], axis=-1)
    assert rel(got[..., :4], want) < 1e-6
    by_ref = ref.turn(jnp.asarray(x[0].transpose(1, 0, 2)), pos, 1e7, 4)  # [T, H, D]
    assert rel(got[0].transpose(1, 0, 2), by_ref) < 1e-6
    assert np.array_equal(got[..., 0, :], x[..., 0, :])  # position 0 is not turned


def test_the_zero_centred_norm_and_the_gates(rng):
    x = rng.standard_normal((7, 3, 8)).astype(np.float32)
    gate = rng.standard_normal((7, 3, 8)).astype(np.float32)
    w = (rng.standard_normal(8) * 0.3).astype(np.float32)
    hat = x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-6)
    assert rel(nnseq.centred_rms_norm(jnp.asarray(x), jnp.asarray(w), 1e-6), hat * (1.0 + w)) < 1e-6
    assert rel(nnseq.centred_rms_norm(jnp.asarray(x), jnp.zeros(8), 1e-6), hat) < 1e-6
    assert rel(ref.norm(jnp.asarray(x), jnp.asarray(w), 1e-6), hat * (1.0 + w)) < 1e-6
    silu = gate / (1.0 + np.exp(-gate))
    assert rel(nnseq.gated_rms_norm(jnp.asarray(x), jnp.asarray(w), jnp.asarray(gate), 1e-6,
                                    activation=jax.nn.silu), hat * w * silu) < 1e-6
    assert rel(nnseq.gated_rms_norm(jnp.asarray(x), jnp.asarray(w), jnp.asarray(gate), 1e-6),
               hat * w / (1.0 + np.exp(-gate))) < 1e-6  # the default stays the sigmoid
    assert rel(nnseq.sigmoid_gate(jnp.asarray(x), jnp.asarray(gate)), x / (1.0 + np.exp(-gate))) < 1e-6


# ---- the dialect

def _cfg(**keys):
    cfg = InputInfo()
    cfg.seq_layers, cfg.seq_length, cfg.seq_batch, cfg.kda_chunk = 4, 32, 2, 16
    for k, v in keys.items():
        setattr(cfg, k, v)
    return cfg


def test_a_qwen3_next_file_is_a_stack_without_a_dense_layer():
    spec = seqlm.SeqSpec.from_cfg(MODEL, _cfg())
    assert spec.mixers == ("kda", "kda", "kda", "gqa") and spec.kda_layers == 3
    assert (spec.dense_layers, spec.moe_layers) == (0, 4)
    assert spec.runs == (("moe", "kda", 0, 3), ("moe1", "gqa", 3, 1))
    assert (spec.heads, spec.kv_heads, spec.v_head, spec.nope, spec.rope) == (4, 2, 16, 12, 4)
    assert (spec.kda_heads, spec.kda_value_heads, spec.kda_dim, spec.conv_kernel) == (2, 4, 8, 4)
    assert (spec.decay_per_head, spec.gates_low_rank, spec.out_gate) == (True, False, "silu")
    assert (spec.scoring, spec.shared_gate, spec.centred_norms) == ("softmax", True, True)
    assert (spec.routed, spec.per_token, spec.shared_width, spec.route_scale) == (32, 3, 24, 1.0)
    assert (spec.theta, spec.eps) == (1e7, 1e-6)
    assert seqlm.SeqSpec.from_cfg(MODEL, _cfg(seq_layers=1)).mixers == ("kda",)
    eight = seqlm.SeqSpec.from_cfg(MODEL, _cfg(seq_layers=8))
    assert eight.mixers == ("kda", "kda", "kda", "gqa") * 2
    assert [r[:2] for r in eight.runs] == [("moe", "kda"), ("moe1", "gqa"), ("moe2", "kda"), ("moe3", "gqa")]
    # a file that only carries the pattern's key is the same dialect
    assert seqlm.SeqSpec.from_cfg({k: v for k, v in MODEL.items() if k != "model_type"}, _cfg()) == spec


@pytest.mark.parametrize("key, value", [
    ("mlp_only_layers", [0]), ("decoder_sparse_step", 2), ("norm_topk_prob", False),
    ("rope_scaling", {"type": "yarn", "factor": 4.0}), ("use_sliding_window", True),
    ("hidden_act", "gelu"),
])
def test_a_qwen3_next_key_the_family_does_not_compute_is_refused_by_name(key, value):
    with pytest.raises(ValueError, match=f"MODEL_FILE has {key}="):
        seqlm.SeqSpec.from_cfg(dict(MODEL, **{key: value}), _cfg())


@pytest.mark.parametrize("keys, message", [
    (dict(seq_layers=0, seq_length=32), None),  # 0 means all the model's layers
    (dict(seq_layers=9), "SEQ_LAYERS:9 must keep at least one expert layer"),
    (dict(expert_shards=5), "EXPERT_SHARDS:5 must divide the 32 routed experts"),
    (dict(kda_chunk=5), "KDA_CHUNK:5 does not divide"),
])
def test_the_cut_of_a_qwen3_next_file_is_checked(keys, message):
    if message is None:
        assert len(seqlm.SeqSpec.from_cfg(MODEL, _cfg(**keys)).mixers) == 8
        return
    with pytest.raises(ValueError, match=message):
        seqlm.SeqSpec.from_cfg(MODEL, _cfg(**keys))


def test_heads_that_do_not_share_evenly_are_refused():
    with pytest.raises(ValueError, match="heads do not share evenly"):
        seqlm.SeqSpec.from_cfg(dict(MODEL, num_key_value_heads=3), _cfg())
    with pytest.raises(ValueError, match="heads do not share evenly"):
        seqlm.SeqSpec.from_cfg(dict(MODEL, linear_num_value_heads=3), _cfg())


# ---- the mixers and the expert MLP against the reference's, one layer

@pytest.fixture(scope="module")
def stack(tmp_path_factory):
    """A float32 trainer over delta rule x3, gated attention (no dense
    layer), and its initial weights with every norm moved off its start."""
    trainer = make_trainer(tmp_path_factory.mktemp("gdn"))
    return trainer, _bumped(trainer.params, np.random.default_rng(1))


def _layer(params, run, at=0):
    return jax.tree.map(lambda a: a[at], params[run])


def test_the_delta_mixer_is_the_reference_mixer(stack, rng):
    trainer, p0 = stack
    lp = _layer(p0, "moe", 1)
    x = rng.standard_normal((64, SHAPE.hidden)).astype(np.float32)
    got = seqlm.delta_attention(lp, jnp.asarray(x), trainer.spec, compute_cast(None), jnp.float32)
    want = np.concatenate([np.asarray(ref.delta_mixer(lp, jnp.asarray(x[lo: lo + 32]), SHAPE))
                           for lo in (0, 32)])
    assert rel(got, want) < 1e-5
    other = x.copy()
    other[:32] += 1.0  # a sequence's rows do not depend on the other sequence of the batch
    moved = seqlm.delta_attention(lp, jnp.asarray(other), trainer.spec, compute_cast(None), jnp.float32)
    assert np.array_equal(np.asarray(moved)[32:], np.asarray(got)[32:])


def test_the_gated_attention_is_the_reference_mixer(stack, rng):
    trainer, p0 = stack
    lp = _layer(p0, "moe1")
    x = rng.standard_normal((64, SHAPE.hidden)).astype(np.float32)
    got = seqlm.gated_attention(lp, jnp.asarray(x), trainer.spec, compute_cast(None), jnp.float32)
    want = np.concatenate([np.asarray(ref.mixer(lp, jnp.asarray(x[lo: lo + 32]), SHAPE, None, 8))
                           for lo in (0, 32)])
    assert rel(got, want) < 1e-5
    for changed in (dict(rope=16, nope=0), dict(rope=8, nope=8)):  # what rotary turns is in the result
        turned = seqlm.gated_attention(lp, jnp.asarray(x), dataclasses.replace(trainer.spec, **changed),
                                       compute_cast(None), jnp.float32)
        assert rel(np.asarray(turned) - x, want - x) > 2e-3  # the layer's own part, without the stream


def test_softmax_routing_and_the_gated_shared_expert_are_the_reference(stack, rng):
    trainer, p0 = stack
    lp = _layer(p0, "moe1")
    spec, share = trainer.spec, ref.Share(trainer.spec.first, trainer.spec.held)
    x = rng.standard_normal((40, SHAPE.hidden)).astype(np.float32)
    bias = jnp.zeros((spec.routed,), jnp.float32)
    got, sizes, choice = seqlm.expert_mlp(lp, bias, jnp.asarray(x), spec, compute_cast(None))
    want, own = ref.expert_mlp(lp, jnp.asarray(x), bias, SHAPE, share)
    assert np.array_equal(np.sort(np.asarray(choice), -1), np.sort(np.asarray(own), -1))
    assert rel(got, want) < 1e-5
    held = (np.asarray(choice) >= spec.first) & (np.asarray(choice) < spec.first + spec.held)
    assert int(np.asarray(sizes).sum()) == int(held.sum())
    # the weights are the softmax's, renormalised over the chosen: they sum to one
    hn = nnseq.centred_rms_norm(jnp.asarray(x), lp["norm2"], spec.eps)
    p = jax.nn.softmax(nnseq.matmul(hn, lp["router"], lambda t: t), axis=-1)
    _, weight = seqlm.moe.route(p, bias, spec.per_token, spec.route_scale)
    assert rel(weight.sum(-1), np.ones(40)) < 1e-6
    assert rel(weight, np.take_along_axis(np.asarray(p), np.asarray(choice), -1)
               / np.take_along_axis(np.asarray(p), np.asarray(choice), -1).sum(-1, keepdims=True)) < 1e-6
    for changed in (dict(scoring="sigmoid"), dict(shared_gate=False)):
        other, _, _ = seqlm.expert_mlp(lp, bias, jnp.asarray(x), dataclasses.replace(spec, **changed),
                                       compute_cast(None))
        assert rel(np.asarray(other) - x, np.asarray(want) - x) > 1e-2


def test_the_sixteen_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer(rng):
    d, w, sw = SHAPE.hidden, MODEL["moe_intermediate_size"], MODEL["shared_expert_intermediate_size"]
    n = lambda *s: (rng.standard_normal(s) * 0.3).astype(np.float32)  # noqa: E731
    lp = {"norm2": n(d), "router": n(d, 32), "eg": n(32, d, w), "eu": n(32, d, w), "ed": n(32, w, d),
          "sg": n(d, sw), "su": n(d, sw), "sd": n(sw, d), "sgate": n(d, 1)}
    x = rng.standard_normal((40, d)).astype(np.float32)
    bias = jnp.zeros((32,), jnp.float32)
    whole, _ = ref.expert_mlp(lp, jnp.asarray(x), bias, SHAPE, ref.Share(0, 32))
    _, shared, _ = ref.expert_parts(lp, jnp.asarray(x), bias, SHAPE, ref.Share(0, 0))
    cast = compute_cast(None)
    total, rows = x + np.asarray(shared), 0
    for shard in range(16):
        spec = seqlm.SeqSpec.from_cfg(MODEL, _cfg(seq_length=40, seq_batch=1, expert_shards=16,
                                                  expert_shard=shard, kda_chunk=8))
        assert (spec.first, spec.held) == (2 * shard, 2)
        mine = dict(lp, **{k: lp[k][2 * shard: 2 * shard + 2] for k in ("eg", "eu", "ed")})
        out, sizes, _ = seqlm.expert_mlp(mine, bias, jnp.asarray(x), spec, cast)
        total = total + (np.asarray(out) - x - np.asarray(shared))  # the routed part of this share
        rows += int(np.asarray(sizes).sum())
    assert rows == 40 * 3  # every pair was computed by exactly one share
    assert rel(total, whole) < 1e-5


# ---- the stack against the reference

def _by_sequence(choice, sequences):
    c = np.asarray(choice)
    return c.reshape(c.shape[0], sequences, -1, c.shape[-1]).transpose(1, 0, 2, 3)


def test_the_stack_without_a_dense_layer_matches_the_reference(stack):
    """The tree, the loss, every gradient leaf of every layer (both mixers,
    the experts, embedding, head), and the tail's gradients as the chip's
    check computes them."""
    trainer, p0 = stack
    assert set(trainer.params) == {"embed", "moe", "moe1", "norm", "head"}  # no "dense"
    delta = {"norm1", "wq", "wk", "wv", "cq", "ck", "cv", "wf", "a_log", "dt_bias", "wb", "wz",
             "o_norm", "wo", "norm2"}
    attending = {"norm1", "wq", "wk", "wv", "q_norm", "k_norm", "wo", "norm2"}
    experts = {"router", "eg", "eu", "ed", "sg", "su", "sd", "sgate"}
    assert set(trainer.params["moe"]) == delta | experts
    assert set(trainer.params["moe1"]) == attending | experts
    shapes = {k: v.shape for k, v in trainer.params["moe"].items()}
    assert (shapes["wq"], shapes["wv"], shapes["wf"]) == ((3, 48, 16), (3, 48, 32), (3, 48, 4))
    assert (shapes["a_log"], shapes["dt_bias"], shapes["cv"]) == ((3, 4), (3, 4), (3, 32, 4))
    assert trainer.params["moe1"]["wq"].shape == (1, 48, 4 * 2 * 16)
    # norms start at zero (zero-centred), the delta rule's output norm at one
    assert not np.any(trainer.params["norm"]) and not np.any(trainer.params["moe"]["norm1"])
    assert not np.any(trainer.params["moe1"]["q_norm"]) and np.all(trainer.params["moe"]["o_norm"] == 1)

    params = jax.tree.map(jnp.asarray, p0)
    batch = trainer.datum.tokens[:2]
    (loss, (_, choice)), grads = jax.value_and_grad(trainer._loss, has_aux=True)(
        params, trainer.route_bias, jnp.asarray(batch))
    share = ref.Share(trainer.spec.first, trainer.spec.held)
    follow = _by_sequence(choice, 2)
    want_loss, want = ref.loss_and_grads(params, batch, SHAPE, share, None, follow)
    assert abs(float(loss) - float(want_loss)) < 1e-5 * float(want_loss)
    errors = jax.tree_util.tree_leaves_with_path(jax.tree.map(rel, grads, want))
    assert len(errors) == 2 + 23 + 16 + 1 and max(e for _, e in errors) < 2e-5, sorted(
        errors, key=lambda kv: -kv[1])[:3]
    # the reference's own choice is the program's, and the blocked forward is the whole one
    blocked, own = ref.loss(params, batch, SHAPE, share, None, None, 16)
    assert abs(float(blocked) - float(want_loss)) < 1e-5 * float(want_loss)
    assert np.array_equal(np.sort(np.asarray(own), -1), np.sort(follow, -1))
    # the tail (the last delta-rule layer, the attention layer, norm, head) in blocks
    tail_loss, tail = ref.tail_loss_and_grads(params, batch, SHAPE, share, None, follow, 16)
    assert abs(float(tail_loss) - float(want_loss)) < 1e-5 * float(want_loss)
    whole = {"layers": ref._pruned([_layer(want, "moe", 2), _layer(want, "moe1")]), "norm": want["norm"],
             "head": want["head"]}
    assert max(jax.tree.leaves(jax.tree.map(rel, tail, whole))) < 2e-5
    assert jax.tree.structure(tail) == jax.tree.structure(ref.tail_of(params))
    assert "eg" in tail["layers"][1] and "eg" not in tail["layers"][0] and "sg" in tail["layers"][0]
    assert "a_log" not in tail["layers"][0] and "dt_bias" not in tail["layers"][0] and "wf" in tail["layers"][0]
    assert ref._pruned([{"eg": np.zeros((32, 2, 2)), "wq": 1}] * 2)[1]["eg"].shape == (ref.TAIL_EXPERTS, 2, 2)


def test_the_trainer_runs_counts_and_names_its_scopes(tmp_path):
    trainer = make_trainer(tmp_path)
    trainer.run()
    assert len(trainer.loss_history) == 2 and np.all(np.isfinite(trainer.loss_history))
    gauges = trainer.metrics.snapshot()["gauges"]
    assert (gauges["seq.kda_layers"], gauges["seq.gqa_layers"], gauges["seq.mla_layers"]) == (3, 1, 0)
    assert (gauges["kda.key_heads"], gauges["kda.value_heads"], gauges["kda.decay_per_head"],
            gauges["kda.chunk"]) == (2, 4, 1, 16)
    assert (gauges["kda.recur_fused"], gauges["kda.rows_per_block"]) == (0.0, 0)
    assert (gauges["attn.kernel"], gauges["attn.rows_per_tile"]) == (0.0, 0)
    assert trainer.metrics.counter_get("kda.token_layers") == 2 * 64 * 3  # epochs x tokens x layers
    assert trainer.metrics.counter_get("gqa.token_layers") == 2 * 64
    assert trainer.metrics.counter_get("seq.tokens") == 2 * 64
    assert trainer.route_bias.shape == (4, 32) and len(trainer.routed_history) == 2
    table = trainer.scope_table()
    assert set(table.values()) == set(seqlm.SCOPES) - {"seq/mla/project", "seq/mla/attend",
                                                       "seq/dense_mlp", "seq/sconv/project",
                                                       "seq/sconv/conv", "seq/sconv/out"}
    assert seqlm.scope_of("jit(step)/transpose(jvp(seq/gqa/attend))/while/body/dot") == "seq/gqa/attend"


def test_bfloat16_compute_stays_near_the_reference(tmp_path):
    trainer = make_trainer(tmp_path, PRECISION="bfloat16")
    p0 = jax.tree.map(np.asarray, trainer.params)
    trainer.run()
    share = ref.Share(trainer.spec.first, trainer.spec.held)
    want, _ = ref.loss(p0, trainer.datum.tokens[:2], SHAPE, share, block=16)
    assert abs(trainer.loss_history[0] - float(want)) < 1e-3 * float(want)
    assert all(a.dtype == jnp.float32 for a in jax.tree.leaves(trainer.params))  # the masters
