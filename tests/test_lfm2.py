"""The fourth dialect of the token-sequence family (an ``lfm2_moe`` file): the
gated short convolution (models/seqlm.py's ``short_conv`` over nn/seq.py's
``causal_conv``), grouped-query attention without an output gate and with
rotary over the whole head, two leading dense layers, a sigmoid router with
a selection bias and no shared expert, against the plain reference the
benchmark compares with (benchmark/reference/lfm2.py, loaded by its path:
one reference, no second copy), on the CPU, small widths, seeded weights."""

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
from neutronstarlite_tpu.nn.layers import compute_cast
from neutronstarlite_tpu.utils.config import InputInfo

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_reference():
    path = os.path.join(REPO, "benchmark", "reference", "lfm2.py")
    spec = importlib.util.spec_from_file_location("reference_lfm2", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _load_reference()

PATTERN = ["conv", "conv", "full_attention", "conv", "conv", "conv", "full_attention", "conv",
           "conv", "conv"]
# the keys of an lfm2_moe config.json, small: ten layers, the published pattern's first ten
MODEL = dict(
    model_type="lfm2_moe", hidden_size=48, num_attention_heads=4, num_key_value_heads=2,
    conv_L_cache=3, conv_bias=False, intermediate_size=96, moe_intermediate_size=32,
    layer_types=PATTERN, num_dense_layers=2, num_experts=16, num_experts_per_tok=4,
    norm_topk_prob=True, use_expert_bias=True, routed_scaling_factor=1, norm_eps=1e-5,
    rope_parameters={"rope_theta": 1000000, "rope_type": "default"}, num_hidden_layers=10,
    vocab_size=128, max_position_embeddings=64,
)
SHAPE = ref.Shape.of(MODEL)
CUT = dict(SEQ_LAYERS=6, SEQ_LENGTH=32, SEQ_BATCH=2, SEQ_CORPUS=3, EXPERT_SHARDS=4,
           EXPERT_SHARD=1, VOCAB_SHARDS=2, ATTN_BLOCK=8, LOSS_CHUNK=16, EPOCHS=2,
           LEARN_RATE=0.0003, WEIGHT_DECAY=0.0001, DECAY_EPOCH=-1)


def make_trainer(tmp_path, **keys):
    """test_kda's trainer from a cfg file beside its model JSON, over this
    file's model and cut."""
    return test_kda.make_trainer(tmp_path, model=MODEL, **dict(CUT, **keys))


def _bumped(params, rng):
    """``params`` with every norm weight moved off its start (one)."""
    def bump(path, a):
        if "norm" in jax.tree_util.keystr(path):
            return a + rng.normal(0.0, 0.3, a.shape).astype(np.float32)
        return a
    return jax.tree_util.tree_map_with_path(bump, jax.tree.map(np.asarray, params))


def _cfg(**keys):
    cfg = InputInfo()
    cfg.seq_layers, cfg.seq_length, cfg.seq_batch = 6, 32, 2
    for k, v in keys.items():
        setattr(cfg, k, v)
    return cfg


# ---- the dialect

def test_an_lfm2_moe_file_is_two_dense_layers_then_expert_layers():
    spec = seqlm.SeqSpec.from_cfg(MODEL, _cfg())
    assert spec.mixers == ("sconv", "sconv", "gqa", "sconv", "sconv", "sconv")
    assert (spec.dense_layers, spec.moe_layers) == (2, 4)
    assert spec.runs == (("moe", "gqa", 0, 1), ("moe1", "sconv", 1, 3))
    assert (spec.heads, spec.kv_heads, spec.v_head, spec.nope, spec.rope) == (4, 2, 12, 0, 12)
    assert (spec.attn_gate, spec.centred_norms, spec.conv_kernel) == (False, False, 3)
    assert (spec.scoring, spec.shared_width, spec.shared_gate) == ("sigmoid", 0, False)
    assert (spec.routed, spec.per_token, spec.route_scale, spec.route_eps) == (16, 4, 1.0, 1e-6)
    assert (spec.theta, spec.eps, spec.ffn, spec.expert_width) == (1e6, 1e-5, 96, 32)
    assert seqlm.dialect_of(MODEL).name == "lfm2_moe"
    assert [d.name for d in seqlm.DIALECTS] == ["lfm2_moe", "qwen3_next", "kimi_linear", "deepseek_v3"]
    assert seqlm.SeqSpec.from_cfg(MODEL, _cfg(seq_layers=3)).mixers == ("sconv", "sconv", "gqa")


@pytest.mark.parametrize("key, value", [
    ("conv_bias", True), ("use_expert_bias", False), ("tie_word_embeddings", True),
    ("block_auto_adjust_ff_dim", True), ("norm_topk_prob", False), ("hidden_act", "gelu"),
    ("rope_parameters", {"rope_theta": 1000000, "rope_type": "yarn"}),
])
def test_an_lfm2_moe_key_the_family_does_not_compute_is_refused_by_name(key, value):
    name = "rope_parameters.rope_type" if key == "rope_parameters" else key
    with pytest.raises(ValueError, match=f"MODEL_FILE has {name}="):
        seqlm.SeqSpec.from_cfg(dict(MODEL, **{key: value}), _cfg())


@pytest.mark.parametrize("model, keys, message", [
    (MODEL, dict(seq_layers=2), "SEQ_LAYERS:2 must keep the 2 dense layers and at least one expert"),
    (MODEL, dict(seq_layers=11), "SEQ_LAYERS:11 must keep"),
    (dict(MODEL, layer_types=PATTERN[:-1] + ["sliding_attention"]), {},
     "layer_types has 'sliding_attention'"),
    (dict(MODEL, layer_types=PATTERN[:-1]), {}, "layer_types names 9 layers"),
    (dict(MODEL, num_key_value_heads=3), {}, "heads do not share evenly"),
    (MODEL, dict(expert_shards=5), "EXPERT_SHARDS:5 must divide the 16 routed experts"),
])
def test_the_cut_of_an_lfm2_moe_file_is_checked(model, keys, message):
    with pytest.raises(ValueError, match=message):
        seqlm.SeqSpec.from_cfg(model, _cfg(**keys))


# ---- the mixers against the reference's, one layer

@pytest.fixture(scope="module")
def stack(tmp_path_factory):
    """A float32 trainer over conv + dense x2, attention + experts, conv +
    experts x3, and its initial weights with every norm moved off one."""
    trainer = make_trainer(tmp_path_factory.mktemp("lfm2"))
    return trainer, _bumped(trainer.params, np.random.default_rng(1))


def _layer(params, run, at=0):
    return jax.tree.map(lambda a: a[at], params[run])


def test_the_short_convolution_is_the_reference_mixer(stack, rng):
    trainer, p0 = stack
    spec = trainer.spec
    x = jnp.asarray(rng.standard_normal((spec.tokens, spec.hidden)).astype(np.float32))
    cast = compute_cast(None)
    for lp in (p0["dense"], _layer(p0, "moe1", 2)):
        got = seqlm.short_conv(lp, x, spec, cast, jnp.float32)
        want = jnp.concatenate([ref.conv_mixer(lp, x[s * 32:(s + 1) * 32], SHAPE) for s in range(2)])
        assert rel(got, want) < 1e-5
    # a position reads itself and the two before it in its own sequence, nothing later
    lp = p0["dense"]
    base = seqlm.short_conv(lp, x, spec, cast, jnp.float32)
    for at, moved in ((5, range(5, 8)), (32, range(32, 35)), (31, range(31, 32))):
        y = seqlm.short_conv(lp, x.at[at].add(1.0), spec, cast, jnp.float32)
        changed = np.flatnonzero(np.any(np.asarray(y != base), axis=-1))
        assert list(changed) == list(moved), (at, changed)


def test_the_attention_without_a_gate_is_the_reference_mixer(stack, rng):
    trainer, p0 = stack
    spec = trainer.spec
    lp = _layer(p0, "moe")
    assert lp["wq"].shape == (48, 4 * 12) and lp["q_norm"].shape == (12,)  # no gate half
    x = jnp.asarray(rng.standard_normal((spec.tokens, spec.hidden)).astype(np.float32))
    got = seqlm.gated_attention(lp, x, spec, compute_cast(None), jnp.float32)
    want = jnp.concatenate([ref.mixer(lp, x[s * 32:(s + 1) * 32], "full_attention", SHAPE, block=8)
                            for s in range(2)])
    assert rel(got, want) < 1e-5


def test_the_eight_shares_add_up_to_the_uncut_layer(rng):
    """No shared expert: what every chip computes alike is the residual
    stream, counted once; the eight shares' routed parts add up to the
    uncut reference's layer, the bias moving the choice in both."""
    d, w = SHAPE.hidden, MODEL["moe_intermediate_size"]
    n = lambda *s: (rng.standard_normal(s) * 0.3).astype(np.float32)  # noqa: E731
    lp = {"norm2": 1.0 + n(d), "router": n(d, 16), "eg": n(16, d, w), "eu": n(16, d, w),
          "ed": n(16, w, d)}
    x = rng.standard_normal((40, d)).astype(np.float32)
    bias = jnp.asarray(n(16))
    whole, own = ref.expert_mlp(lp, jnp.asarray(x), bias, SHAPE, ref.Share(0, 16))
    unbiased = ref.choose(ref.router_scores(lp, ref.norm(jnp.asarray(x), lp["norm2"], SHAPE.eps)),
                          0.0, 4)
    assert np.any(np.sort(np.asarray(own), -1) != np.sort(np.asarray(unbiased), -1))
    cast = compute_cast(None)
    total, rows = np.array(x), 0
    for shard in range(8):
        spec = seqlm.SeqSpec.from_cfg(MODEL, _cfg(seq_length=40, seq_batch=1, expert_shards=8,
                                                  expert_shard=shard))
        assert (spec.first, spec.held) == (2 * shard, 2)
        mine = dict(lp, **{k: lp[k][2 * shard: 2 * shard + 2] for k in ("eg", "eu", "ed")})
        out, sizes, choice = seqlm.expert_mlp(mine, bias, jnp.asarray(x), spec, cast)
        assert np.array_equal(np.sort(np.asarray(choice), -1), np.sort(np.asarray(own), -1))
        total = total + (np.asarray(out) - x)  # the routed part of this share
        rows += int(np.asarray(sizes).sum())
    assert rows == 40 * 4  # every pair was computed by exactly one share
    assert rel(total, whole) < 1e-5


# ---- the stack against the reference

def _by_sequence(choice, sequences):
    c = np.asarray(choice)
    return c.reshape(c.shape[0], sequences, -1, c.shape[-1]).transpose(1, 0, 2, 3)


def test_the_stack_matches_the_reference(stack):
    """The tree, the logits, the loss, every gradient leaf of every layer
    (both mixers, both MLPs, embedding, head), and the tail's gradients as
    the chip's check computes them, under a bias that moves the choice."""
    trainer, p0 = stack
    assert set(trainer.params) == {"embed", "dense", "dense1", "moe", "moe1", "norm", "head"}
    conv, attending = {"norm1", "win", "conv", "wout"}, {"norm1", "wq", "wk", "wv", "q_norm", "k_norm", "wo"}
    assert set(trainer.params["dense"]) == set(trainer.params["dense1"]) == conv | {"norm2", "wg", "wu", "wd"}
    experts = {"norm2", "router", "eg", "eu", "ed"}  # no shared expert
    assert set(trainer.params["moe"]) == attending | experts and set(trainer.params["moe1"]) == conv | experts
    assert trainer.params["moe1"]["win"].shape == (3, 48, 144)
    assert trainer.params["moe1"]["conv"].shape == (3, 48, 3)
    assert np.all(trainer.params["norm"] == 1) and np.all(trainer.params["moe"]["q_norm"] == 1)
    assert ref.layout_faults(p0, SHAPE) == []

    params = jax.tree.map(jnp.asarray, p0)
    bias = jnp.asarray(np.random.default_rng(5).normal(0.0, 0.05, (4, 16)).astype(np.float32))
    batch = trainer.datum.tokens[:2]
    logits, choice = trainer._eval_logits(params, bias, jnp.asarray(batch), jnp.arange(64))
    share = ref.Share(trainer.spec.first, trainer.spec.held)
    follow = _by_sequence(choice, 2)
    want_logits = np.concatenate([np.asarray(ref.head_logits(
        p0, ref.hidden_states(params, batch[s], SHAPE, share, bias, follow[s], 8)[0], SHAPE))
        for s in range(2)])
    assert np.abs(np.asarray(logits) - want_logits).max() / np.abs(want_logits).max() < 1e-5
    (loss, (_, choice)), grads = jax.value_and_grad(trainer._loss, has_aux=True)(
        params, bias, jnp.asarray(batch))
    follow = _by_sequence(choice, 2)
    want_loss, want = ref.loss_and_grads(params, batch, SHAPE, share, bias, follow)
    assert abs(float(loss) - float(want_loss)) < 1e-5 * float(want_loss)
    errors = jax.tree_util.tree_leaves_with_path(jax.tree.map(rel, grads, want))
    assert len(errors) == 2 + 1 + 2 * 8 + 12 + 9 and max(e for _, e in errors) < 2e-5, sorted(
        errors, key=lambda kv: -kv[1])[:3]
    # the reference's own choice is the program's, the bias included; the blocked forward is the whole one
    blocked, own = ref.loss(params, batch, SHAPE, share, bias, None, 16)
    assert abs(float(blocked) - float(want_loss)) < 1e-5 * float(want_loss)
    assert np.array_equal(np.sort(np.asarray(own), -1), np.sort(follow, -1))
    # the tail (the second dense layer, the attention layer, the three convolution layers, norm,
    # head) in blocks
    tail_loss, tail = ref.tail_loss_and_grads(params, batch, SHAPE, share, bias, follow, 16)
    assert abs(float(tail_loss) - float(want_loss)) < 1e-5 * float(want_loss)
    whole = {"dense": [want["dense1"]],
             "layers": ref._pruned([_layer(want, "moe")] + [_layer(want, "moe1", i) for i in range(3)]),
             "norm": want["norm"], "head": want["head"]}
    assert max(jax.tree.leaves(jax.tree.map(rel, tail, whole))) < 2e-5
    assert jax.tree.structure(tail) == jax.tree.structure(ref.tail_of(params))
    assert "q_norm" in tail["layers"][0] and "eg" not in tail["layers"][0] and "eg" in tail["layers"][3]
    assert set(tail["dense"][0]) == conv | {"norm2", "wg", "wu", "wd"}


def test_two_adam_steps_follow_the_reference(stack):
    trainer, _ = stack
    share = ref.Share(trainer.spec.first, trainer.spec.held)
    params, opt = trainer.initial_state()
    p0 = jax.tree.map(np.asarray, params)
    want = jax.tree.map(lambda a: np.asarray(a, np.float64), p0)
    m = v = jax.tree.map(np.zeros_like, want)
    for step in range(2):
        batch = trainer.datum.tokens[2 * step: 2 * step + 2]
        params, opt, _, _, choice = trainer._train_step(params, opt, trainer.route_bias, trainer.corpus,
                                                        trainer._batch_index[step])
        _, grads = ref.loss_and_grads(want, batch, SHAPE, share, None, _by_sequence(choice, 2))
        leaves, tree = jax.tree.flatten(want)
        out = [ref.adam_step(p, g, a, b, step + 1, 0.0003, 0.0001)
               for p, g, a, b in zip(leaves, tree.flatten_up_to(grads), tree.flatten_up_to(m),
                                     tree.flatten_up_to(v))]
        want, m, v = (tree.unflatten([o[i] for o in out]) for i in range(3))
        moved = jax.tree.map(lambda a, b, c: rel(np.asarray(a, np.float64) - c, b - c), params, want, p0)
        assert max(jax.tree.leaves(moved)) < 5e-3, (step, moved)


@pytest.mark.parametrize("layers, dense, first", [
    (6, 1, 0),  # the cut: the second dense layer and every expert layer
    (8, 2, 2),  # more expert layers than the tail: no dense layer in it
    (4, 1, 0),  # two expert layers: the tail is still the end of the stack
])
def test_the_tail_is_the_end_of_the_stack(layers, dense, first):
    spec = seqlm.SeqSpec.from_cfg(MODEL, _cfg(seq_layers=layers))
    shapes = jax.eval_shape(lambda k: seqlm.init_params(k, spec), jax.random.PRNGKey(0))
    assert ref._tail_starts(shapes) == (dense, first)
    assert len(jax.eval_shape(ref.tail_of, shapes)["dense"]) == 2 - dense


@pytest.mark.parametrize("score", [1e-7, 0.5])
def test_the_chosen_weights_are_divided_by_their_sum_plus_the_files_eps(score):
    from neutronstarlite_tpu.ops import moe

    spec = seqlm.SeqSpec.from_cfg(MODEL, _cfg())
    assert spec.route_eps == 1e-6 and seqlm.SeqSpec.route_eps == moe.ROUTE_EPS  # the others keep 1e-20
    _, weight = moe.route(jnp.full((3, 16), score, jnp.float32), jnp.zeros((16,)), spec.per_token,
                          spec.route_scale, eps=spec.route_eps)
    np.testing.assert_allclose(np.asarray(weight), score / (4 * score + 1e-6), rtol=1e-6)


def test_a_tree_that_is_not_the_files_stack_is_named():
    spec = seqlm.SeqSpec.from_cfg(MODEL, _cfg())
    shapes = jax.eval_shape(lambda k: seqlm.init_params(k, spec), jax.random.PRNGKey(0))
    assert ref.layout_faults(shapes, SHAPE) == []
    one_dense = jax.eval_shape(lambda k: seqlm.init_params(k, dataclasses.replace(
        spec, dense_layers=1, moe_layers=5)), jax.random.PRNGKey(0))
    assert "1 dense layers" in ref.layout_faults(one_dense, SHAPE)[0]
    shared = jax.eval_shape(lambda k: seqlm.init_params(k, dataclasses.replace(
        spec, shared_width=32)), jax.random.PRNGKey(0))
    assert ref.layout_faults(shared, SHAPE)[0] == (
        "layer 2 (full_attention, experts) has beyond the file's ['sd', 'sg', 'su']")
    assert len(ref.layout_faults(shared, SHAPE)) == 4


def test_the_trainer_runs_counts_and_names_its_scopes(tmp_path):
    trainer = make_trainer(tmp_path)
    trainer.run()
    assert len(trainer.loss_history) == 2 and np.all(np.isfinite(trainer.loss_history))
    gauges = trainer.metrics.snapshot()["gauges"]
    assert (gauges["seq.sconv_layers"], gauges["seq.gqa_layers"], gauges["seq.kda_layers"]) == (5, 1, 0)
    assert (gauges["attn.kernel"], gauges["attn.rows_per_tile"]) == (0.0, 0)
    assert trainer.metrics.counter_get("sconv.token_layers") == 2 * 64 * 5  # epochs x tokens x layers
    assert trainer.metrics.counter_get("gqa.token_layers") == 2 * 64
    assert trainer.metrics.counter_get("kda.token_layers") == 0
    assert trainer.route_bias.shape == (4, 16) and len(trainer.routed_history) == 2
    table = trainer.scope_table()
    assert set(table.values()) == set(seqlm.SCOPES) - {
        "seq/mla/project", "seq/mla/attend", "seq/kda/project", "seq/kda/conv", "seq/kda/gate",
        "seq/kda/recur", "seq/kda/out", "seq/moe/shared"}
    assert seqlm.scope_of("jit(step)/transpose(jvp(seq/sconv/conv))/mul") == "seq/sconv/conv"


def test_bfloat16_compute_stays_near_the_reference(tmp_path):
    trainer = make_trainer(tmp_path, PRECISION="bfloat16")
    p0 = jax.tree.map(np.asarray, trainer.params)
    trainer.run()
    share = ref.Share(trainer.spec.first, trainer.spec.held)
    want, _ = ref.loss(p0, trainer.datum.tokens[:2], SHAPE, share, block=16)
    assert abs(trainer.loss_history[0] - float(want)) < 1e-3 * float(want)
    assert all(a.dtype == jnp.float32 for a in jax.tree.leaves(trainer.params))  # the masters
