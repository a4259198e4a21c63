"""The token-sequence family (models/seqlm.py, ops/causal_attention.py,
ops/moe.py, nn/seq.py) against the plain reference the benchmark compares
with (benchmark/reference/moonlight.py, loaded by its path: one reference,
no second copy), on the CPU, float32, small widths, seeded weights."""

import hashlib
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neutronstarlite_tpu.graph.dataset import TokenDatum
from neutronstarlite_tpu.graph.storage import build_graph
from neutronstarlite_tpu.models import get_algorithm, seqlm
from neutronstarlite_tpu.nn.layers import compute_cast
from neutronstarlite_tpu.ops import causal_attention, edge, moe
from neutronstarlite_tpu.ops.causal_attention import causal_edge_attention
from neutronstarlite_tpu.ops.device_graph import DeviceGraph
from neutronstarlite_tpu.utils.config import InputInfo

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_reference():
    path = os.path.join(REPO, "benchmark", "reference", "moonlight.py")
    spec = importlib.util.spec_from_file_location("reference_moonlight", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _load_reference()

MODEL = dict(
    hidden_size=48, num_attention_heads=3, kv_lora_rank=24, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, intermediate_size=96, moe_intermediate_size=32,
    n_shared_experts=2, n_routed_experts=16, num_experts_per_tok=3, routed_scaling_factor=2.446,
    rope_theta=50000, rms_norm_eps=1e-5, num_hidden_layers=6, vocab_size=128,
    max_position_embeddings=64, q_lora_rank=None, n_group=1, topk_group=1,
    scoring_func="sigmoid", first_k_dense_replace=1, moe_layer_freq=1, hidden_act="silu",
)
SHAPE = ref.Shape.of(MODEL)
CUT = dict(SEQ_LAYERS=3, SEQ_LENGTH=32, SEQ_BATCH=2, SEQ_CORPUS=3, EXPERT_SHARDS=4,
           EXPERT_SHARD=1, VOCAB_SHARDS=2, ATTN_BLOCK=8, LOSS_CHUNK=16, EPOCHS=2,
           LEARN_RATE=0.0003, WEIGHT_DECAY=0.0001, DECAY_EPOCH=-1)


def write_cfg(tmp_path, **keys):
    """(cfg path) of a SEQLM cfg file beside its model JSON, as a user's."""
    with open(tmp_path / "model.json", "w") as fh:
        json.dump(MODEL, fh)
    settings = dict(CUT, ALGORITHM="SEQLM", MODEL_FILE="model.json", **keys)
    path = tmp_path / "seq.cfg"
    with open(path, "w") as fh:
        fh.writelines(f"{k}:{v}\n" for k, v in settings.items())
    return str(path)


def make_tokens(seed=0, sequences=6, length=32, vocab=64):
    return np.random.default_rng(seed).integers(0, vocab, size=(sequences, length), dtype=np.int32)


def make_trainer(tmp_path, tokens=None, seed=3, **keys):
    cfg_path = write_cfg(tmp_path, **keys)
    cfg = InputInfo.read_from_cfg_file(cfg_path)
    tokens = make_tokens() if tokens is None else tokens
    return seqlm.SeqLMTrainer.from_tokens(cfg, tokens, seed=seed, base_dir=str(tmp_path))


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    """A float32 trainer, its initial weights, and its first step's state."""
    trainer = make_trainer(tmp_path_factory.mktemp("seq"))
    p0 = jax.tree.map(np.asarray, trainer.params)
    params, opt = trainer.initial_state()
    out = trainer._train_step(params, opt, trainer.route_bias, trainer.corpus,
                              trainer._batch_index[0])
    return trainer, p0, out


# ---- forward and gradients against the reference

def test_eval_forward_matches_reference_logits_and_choice(trained):
    trainer, p0, _ = trained
    batch = trainer.datum.tokens[:2]
    logits, choice = trainer._eval_logits(
        trainer.initial_state()[0], trainer.route_bias, jnp.asarray(batch), jnp.arange(64))
    share = ref.Share(trainer.spec.first, trainer.spec.held)
    want = np.concatenate([
        np.asarray(ref.logits_at(p0, batch[s], np.arange(32), SHAPE, share, block=16))
        for s in range(2)])
    assert np.abs(np.asarray(logits) - want).max() / np.abs(want).max() < 1e-5
    _, own = ref.loss(p0, batch, SHAPE, share, block=16)
    got = np.asarray(choice).reshape(2, 2, 32, 3).transpose(1, 0, 2, 3)
    assert np.array_equal(np.sort(got, -1), np.sort(np.asarray(own), -1))


@pytest.mark.parametrize("group", ["embed", "dense", "moe", "norm", "head"])
def test_step_gradients_match_reference(trained, group):
    """The step's own gradients, read back from Adam's first moment after
    one step from zero state, against ``jax.grad`` of the reference."""
    trainer, p0, (_, opt, loss, _, _) = trained
    share = ref.Share(trainer.spec.first, trainer.spec.held)
    want_loss, want = ref.loss_and_grads(p0, trainer.datum.tokens[:2], SHAPE, share)
    assert abs(float(loss) - float(want_loss)) < 1e-5 * float(want_loss)
    got = jax.tree.map(lambda m, w: np.asarray(m) / 0.1 - 1e-4 * w, opt.m[group], p0[group])
    errors = jax.tree.map(rel, got, want[group])
    assert max(jax.tree.leaves(errors)) < 2e-4, errors


def test_tail_gradients_in_blocks_equal_the_whole_expression(trained):
    trainer, p0, _ = trained
    share = ref.Share(trainer.spec.first, trainer.spec.held)
    batch = trainer.datum.tokens[:2]
    loss, grads = ref.loss_and_grads(p0, batch, SHAPE, share)
    tail_loss, tail = ref.tail_loss_and_grads(p0, batch, SHAPE, share, block=8)
    assert abs(float(loss) - float(tail_loss)) < 1e-5
    want = {"layer": jax.tree.map(lambda a: a[-1], grads["moe"]), "norm": grads["norm"],
            "head": grads["head"]}
    assert max(jax.tree.leaves(jax.tree.map(rel, tail, want))) < 1e-4


# ---- the expert layer: shares, no dropped pair

def _expert_layer(rng, held=16):
    d, w, sw = SHAPE.hidden, MODEL["moe_intermediate_size"], 2 * MODEL["moe_intermediate_size"]
    n = lambda *s: (rng.standard_normal(s) * 0.3).astype(np.float32)  # noqa: E731
    return {"norm2": np.ones(d, np.float32), "router": n(d, 16), "eg": n(held, d, w),
            "eu": n(held, d, w), "ed": n(held, w, d), "sg": n(d, sw), "su": n(d, sw),
            "sd": n(sw, d)}


def _spec(first, held, tokens):
    model = dict(MODEL)
    cfg = InputInfo()
    cfg.seq_layers, cfg.seq_length, cfg.seq_batch = 2, tokens, 1
    cfg.expert_shards, cfg.expert_shard = 16 // held, first // held
    return seqlm.SeqSpec.from_cfg(model, cfg)


def test_the_eight_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer(rng):
    lp = _expert_layer(rng)
    x = rng.standard_normal((40, SHAPE.hidden)).astype(np.float32)
    bias = jnp.zeros((16,), jnp.float32)
    whole, _ = ref.expert_mlp(lp, jnp.asarray(x), bias, SHAPE, ref.Share(0, 16))
    cast = compute_cast(None)
    shared = seqlm.nnseq.swiglu(seqlm.nnseq.rms_norm(x, lp["norm2"], SHAPE.eps),
                                lp["sg"], lp["su"], lp["sd"], cast)
    total, rows = x + np.asarray(shared), 0
    for shard in range(8):
        mine = dict(lp, **{k: lp[k][2 * shard: 2 * shard + 2] for k in ("eg", "eu", "ed")})
        out, sizes, _ = seqlm.expert_mlp(mine, bias, jnp.asarray(x), _spec(2 * shard, 2, 40), cast)
        total = total + (np.asarray(out) - x - np.asarray(shared))
        rows += int(np.asarray(sizes).sum())
    assert rows == 40 * 3  # every pair was computed by exactly one share
    assert rel(total, whole) < 1e-5
    # and one share alone is the reference told the same share
    part, _ = ref.expert_mlp(mine, jnp.asarray(x), bias, SHAPE, ref.Share(14, 2))
    assert rel(out, part) < 1e-5


def test_no_pair_is_dropped_when_the_router_sends_every_token_to_one_held_expert(rng):
    lp = _expert_layer(rng, held=4)
    x = rng.standard_normal((64, SHAPE.hidden)).astype(np.float32)
    bias = jnp.zeros((16,), jnp.float32).at[5].set(10.0)  # expert 5 is held (4..7)
    out, sizes, choice = seqlm.expert_mlp(lp, bias, jnp.asarray(x), _spec(4, 4, 64), compute_cast(None))
    choice = np.asarray(choice)
    assert np.all(np.any(choice == 5, axis=1))
    assert int(np.asarray(sizes)[1]) == 64  # all 64 tokens' pairs reached expert 5
    assert int(np.asarray(sizes).sum()) == int(np.sum((choice >= 4) & (choice < 8)))
    want, _ = ref.expert_mlp(lp, jnp.asarray(x), bias, SHAPE, ref.Share(4, 4))
    assert rel(out, want) < 1e-5


WALK_TOKENS, WALK_CHUNK = 40, 24  # a list of 120 places in five chunks


def _steered(rng, n_routed):
    """(layer, x [40, hidden]) whose router sends exactly ``n_routed`` of the
    120 pairs to the held experts 4..7, the first pairs in token order: the
    router reads the first 16 dims of a token as its scores, and the token
    carries a large value at each expert it is to choose."""
    lp = _expert_layer(rng, held=4)
    lp["router"] = np.eye(SHAPE.hidden, 16, dtype=np.float32)
    x = (rng.standard_normal((WALK_TOKENS, SHAPE.hidden)) * 0.3).astype(np.float32)
    absent = [0, 1, 2, 3, 8, 9, 10, 11, 12, 13, 14, 15]
    for t in range(WALK_TOKENS):
        for slot in range(3):
            is_held = 3 * t + slot < n_routed
            e = 4 + (t + slot) % 4 if is_held else absent[(5 * t + slot) % 12]
            x[t, e] = 4.0 + rng.random()
    return lp, x


@pytest.mark.parametrize("n_routed", [0, 10, 24, 25, 120],
                         ids=["none", "inside_a_chunk", "on_the_boundary", "one_past_it", "all_held"])
def test_the_walk_stops_at_the_routed_rows_and_the_layer_is_the_reference(rng, monkeypatch, n_routed):
    plan_dispatch = moe.plan_dispatch
    monkeypatch.setattr(moe, "plan_dispatch",
                        lambda choice, first, held, _: plan_dispatch(choice, first, held, WALK_CHUNK))
    lp, x = _steered(rng, n_routed)
    bias = jnp.zeros((16,), jnp.float32)
    spec, cast = _spec(4, 4, WALK_TOKENS), compute_cast(None)
    ct = rng.standard_normal(x.shape).astype(np.float32)

    out, sizes, _ = seqlm.expert_mlp(lp, bias, jnp.asarray(x), spec, cast)
    assert int(np.asarray(sizes).sum()) == n_routed
    assert moe.rows_walked(n_routed, 120, WALK_CHUNK) == {0: 0, 10: 24, 24: 24, 25: 48, 120: 120}[n_routed]
    want, _ = ref.expert_mlp(lp, jnp.asarray(x), bias, SHAPE, ref.Share(4, 4))
    assert rel(out, want) < 1e-5

    got = jax.grad(lambda lp, x: jnp.sum(seqlm.expert_mlp(lp, bias, x, spec, cast)[0] * ct),
                   argnums=(0, 1))(lp, jnp.asarray(x))
    want = jax.grad(lambda lp, x: jnp.sum(ref.expert_mlp(lp, x, bias, SHAPE, ref.Share(4, 4))[0] * ct),
                    argnums=(0, 1))(lp, jnp.asarray(x))
    errors = jax.tree.map(lambda g, w: rel(g, w) if np.any(np.asarray(w)) else float(np.abs(g).max()),
                          got, want)
    assert max(jax.tree.leaves(errors)) < 1e-5, errors
    # an expert no routed row reached has a gradient of exact zeros, not what a chunk left behind
    for name in ("eg", "eu", "ed"):
        g = np.asarray(got[0][name])
        assert np.all(np.isfinite(g))
        assert not np.any(g[np.asarray(sizes) == 0])


@pytest.mark.parametrize("n_routed", [0, 25, 120])
def test_the_weights_gradient_follows_the_routed_rows(rng, n_routed):
    """``d weight`` of the walk itself, pair by pair: the held pairs' is the
    cotangent times the expert's row, every other pair's exactly zero."""
    lp, x = _steered(rng, n_routed)
    scores = jax.nn.sigmoid(x[:, :16])
    choice, weight = moe.route(scores, jnp.zeros((16,)), 3, 1.0)
    plan = moe.plan_dispatch(choice, 4, 4, WALK_CHUNK)
    rows = moe.ExpertRows(*(jnp.asarray(a) for a in (x, lp["eg"], lp["eu"], lp["ed"])))
    ct = rng.standard_normal(x.shape).astype(np.float32)
    got = jax.grad(lambda w: jnp.sum(moe.combine_rows(rows, w, plan) * ct))(weight)
    want = np.zeros((WALK_TOKENS, 3), np.float32)
    for t, slot in zip(*np.nonzero(np.asarray(plan.held))):
        e = int(choice[t, slot]) - 4
        row = seqlm.nnseq.swiglu(x[t][None], lp["eg"][e], lp["eu"][e], lp["ed"][e], compute_cast(None))
        want[t, slot] = float(np.asarray(row)[0] @ ct[t])
    assert int(np.asarray(plan.held).sum()) == n_routed
    assert rel(got, want) < 1e-5 if n_routed else not np.any(np.asarray(got))
    assert not np.any(np.asarray(got)[~np.asarray(plan.held)])


def test_rows_routed_counter_equals_the_pairs_sent_to_held_experts(tmp_path):
    trainer = make_trainer(tmp_path, EPOCHS=1)
    trainer.route_bias = trainer.route_bias.at[:, trainer.spec.first].set(10.0)
    p0 = jax.tree.map(np.asarray, trainer.params)
    trainer.run()
    share = ref.Share(trainer.spec.first, trainer.spec.held)
    _, own = ref.loss(p0, trainer.datum.tokens[:2], SHAPE, share, np.asarray(trainer.route_bias))
    own = np.asarray(own)
    held = int(np.sum((own >= share.first) & (own < share.first + share.held)))
    assert held >= 2 * 2 * 32  # every token, both layers, at least the favoured expert
    assert trainer.metrics.counter_get("moe.rows_routed") == held == trainer.routed_history[0]
    assert trainer.metrics.counter_get("seq.tokens") == 64
    gauges = trainer.metrics.snapshot()["gauges"]
    assert gauges["moe.load_max_over_mean"] > 2.0
    # the walk's gauges: two expert layers' lists of 64 x 3 places, each walked to the end
    # of the chunk its routed rows end in
    per_layer = [int(np.sum((own[layer] >= share.first) & (own[layer] < share.first + share.held)))
                 for layer in range(2)]
    chunk = moe.chunk_of(64 * 3)
    assert gauges["moe.list_rows"] == 2 * 64 * 3
    assert gauges["moe.rows_walked"] == sum(-(-n // chunk) * chunk for n in per_layer)


@pytest.mark.parametrize("chunk_rows", [8, 16, 24, 20, 36])
def test_grouped_product_walks_the_list_in_chunks_without_changing_it(rng, chunk_rows):
    """Any step gives the list's sums and their gradients: one that divides
    the list of 48 places, and one that does not (20, 36: the walk pads it
    to whole steps)."""
    x = rng.standard_normal((16, 8)).astype(np.float32)
    rows = moe.ExpertRows(jnp.asarray(x), *(jnp.asarray(rng.standard_normal(s), jnp.float32)
                                            for s in ((3, 8, 6), (3, 8, 6), (3, 6, 8))))
    choice = jnp.asarray(rng.permuted(np.tile(np.arange(6), (16, 1)), axis=1)[:, :3], jnp.int32)
    weight = jnp.asarray(rng.random((16, 3)), jnp.float32)
    plan = moe.plan_dispatch(choice, 1, 3, 48)  # experts 1..3 of 6 are held
    step = moe.plan_dispatch(choice, 1, 3, chunk_rows)
    assert 0 < int(plan.group_sizes.sum()) < 48 and step.chunk == moe.chunk_of(48, chunk_rows) == chunk_rows
    whole = moe.combine_rows(rows, weight, plan)
    chunked = moe.combine_rows(rows, weight, step)
    assert rel(chunked, whole) < 1e-6
    ct = rng.standard_normal(x.shape).astype(np.float32)
    grads = [jax.grad(lambda r, w: jnp.sum(moe.combine_rows(r, w, p) * ct), argnums=(0, 1))(rows, weight)
             for p in (plan, step)]
    for got, want in zip(jax.tree.leaves(grads[1]), jax.tree.leaves(grads[0])):
        assert got.shape == want.shape and rel(got, want) < 1e-6
    # and the list's rows are what a grouped product over the held pairs gives
    sorted_x = x[np.asarray(plan.pair_of) // 3]
    want = np.zeros_like(x)
    y = np.asarray(moe.grouped_swiglu(sorted_x, rows.wg, rows.wu, rows.wd, plan.group_sizes))
    for place in range(int(plan.group_sizes.sum())):
        pair = int(plan.pair_of[place])
        want[pair // 3] += float(weight[pair // 3, pair % 3]) * y[place]
    assert rel(whole, want) < 1e-6


# ---- the implicit causal attention against the explicit edge chain

def _causal_graph(s):
    dst, src = np.nonzero(np.tril(np.ones((s, s), bool)))
    host = build_graph(src.astype(np.uint32), dst.astype(np.uint32), s, weight="ones")
    return DeviceGraph.from_host(host)


def _edge_chain(graph, q, k, v, scale):
    """ops/edge.py's chain per (sequence, head) pair: a score per edge, a
    softmax per destination, a weighted aggregate of the sources."""
    def one(q, k, v):
        score = jnp.sum(q[graph.csc_dst] * k[graph.csc_src], axis=-1) * scale
        alpha = edge.edge_softmax(graph, score)
        return edge.aggregate_edge_to_dst_weighted(graph, alpha, v)
    return jax.vmap(one)(q, k, v)


@pytest.mark.parametrize("block", [8, 16, 48])
def test_causal_attention_equals_the_explicit_edge_chain_forward_and_backward(rng, block):
    n, s, dk, dv = 3, 48, 12, 8
    q, k, v, cot = (jnp.asarray(rng.standard_normal(shape).astype(np.float32))
                    for shape in ((n, s, dk), (n, s, dk), (n, s, dv), (n, s, dv)))
    graph, scale = _causal_graph(s), 0.3
    got, got_vjp = jax.vjp(lambda *a: causal_edge_attention(*a, scale, block), q, k, v)
    want, want_vjp = jax.vjp(lambda *a: _edge_chain(graph, *a, scale), q, k, v)
    assert rel(got, want) < 1e-5
    for g, w in zip(got_vjp(cot), want_vjp(cot)):
        assert rel(g, w) < 1e-5


def test_causal_attention_never_holds_a_square_of_the_sequence():
    n, s = 3, 64
    q = jnp.zeros((n, s, 16))
    v = jnp.zeros((n, s, 8))

    def shapes(jaxpr, out):
        for eqn in jaxpr.eqns:
            out += [tuple(var.aval.shape) for var in eqn.outvars if hasattr(var.aval, "shape")]
            for sub in jax.core.jaxprs_in_params(eqn.params):
                shapes(sub, out)
        return out

    def fwd_bwd(q, k, v):
        return jax.grad(lambda *a: causal_edge_attention(*a, 0.25, 16).sum(), argnums=(0, 1, 2))(q, k, v)

    seen = shapes(jax.make_jaxpr(fwd_bwd)(q, q, v).jaxpr, [])
    assert seen and not [sh for sh in seen if sum(1 for d in sh if d == s) >= 2]
    assert any(sh[-2:] == (16, 16) for sh in seen)  # the tiles are there


@pytest.mark.parametrize("blocks", [1, 2, 5])
def test_a_destination_block_sees_the_source_blocks_up_to_itself_and_no_other(blocks):
    """Equal scores and one-hot values by source block: a destination's
    aggregate is then the share of its in-edges that each block holds."""
    b, s = 4, 4 * blocks
    q = jnp.zeros((1, s, 8))
    v = jnp.asarray(np.repeat(np.eye(blocks, dtype=np.float32), b, axis=0))[None]
    out = np.asarray(causal_edge_attention(q, q, v, 1.0, b))[0]
    for i in range(s):
        want = np.bincount(np.arange(i + 1) // b, minlength=blocks) / (i + 1.0)
        assert np.allclose(out[i], want, atol=1e-6)


# ---- the kernels (Pallas' interpret mode here) against the walk

@pytest.mark.parametrize("n, group, dk, dv, block, heads", [
    (4, 1, 12, 8, 16, 2),  # M's and K's form: group 1, dk != dv, two key heads a grid step
    (2, 4, 8, 8, 8, 4),  # L's: one whole group a step
    (1, 8, 16, 16, 8, 4),  # Q's: the row cap engaged, the group in two parts
])
def test_the_kernels_are_the_walk(rng, monkeypatch, n, group, dk, dv, block, heads):
    """Output, log-normaliser and the three gradients, four blocks of
    positions: masked diagonal tiles and the skipped tiles above them."""
    s = 4 * block
    monkeypatch.setattr(causal_attention, "TILE_BYTES", heads * block * (block + dk + dv) * 4)
    assert causal_attention.tile_heads(n, group, s, block, dk, dv) == heads
    q, k, v, cot = (jnp.asarray(rng.standard_normal(shape).astype(np.float32)) for shape in (
        (n * group, s, dk), (n, s, dk), (n, s, dv), (n * group, s, dv)))
    got = {}
    for form in ("walk", "interpret"):
        out, (*_, lse) = causal_attention._attention_fwd(q, k, v, 0.3, block, group, form)
        _, pull = jax.vjp(lambda *a: causal_attention.attention(*a, 0.3, block, group, form), q, k, v)
        got[form] = (out, lse, *pull(cot))
    for name, a, b in zip(("out", "lse", "dq", "dK", "dV"), got["interpret"], got["walk"]):
        assert a.shape == b.shape and rel(a, b) < 1e-5, name


def test_the_kernels_tile_the_cells_shapes():
    """(key heads, group, positions, block, dk, dv) of M and K, L, Q: 2,048
    query rows a grid step each (Q's group of 8 in two parts); a block off
    the lanes, or a sequence whose kept key and value gradients pass VMEM,
    go to the walk."""
    for shape in ((64, 1, 8192, 512, 192, 128), (32, 4, 8192, 512, 64, 64), (4, 8, 8192, 512, 256, 256)):
        assert causal_attention.kernel_takes(*shape[2:]) and causal_attention.rows_per_tile(*shape) == 2048
    assert not causal_attention.kernel_takes(8192, 64, 64, 64)
    assert not causal_attention.kernel_takes(65536, 512, 192, 128)


# ---- the trainer through run.py's path

def test_two_steps_through_the_cli_path_follow_the_reference(tmp_path):
    from neutronstarlite_tpu.resilience.supervisor import supervised_run

    tokens = make_tokens(seed=5)
    np.save(tmp_path / "tokens.npy", tokens)
    cfg_path = write_cfg(tmp_path, TOKEN_FILE="tokens.npy", WARMUP_EPOCHS=4)
    cfg = InputInfo.read_from_cfg_file(cfg_path)
    toolkit = get_algorithm(cfg.algorithm)(cfg, base_dir=str(tmp_path), seed=7)
    toolkit.init_graph()
    toolkit.init_nn()
    p = jax.tree.map(lambda a: np.asarray(a, np.float64), toolkit.params)
    result = supervised_run(toolkit)
    assert result["loss"] == toolkit.loss_history[-1] and toolkit.run_summary_record is not None
    share = ref.Share(toolkit.spec.first, toolkit.spec.held)
    zeros = jax.tree.map(np.zeros_like, p)
    m, v = zeros, zeros
    for step in range(2):
        loss, grads = ref.loss_and_grads(p, tokens[2 * step: 2 * step + 2], SHAPE, share)
        assert abs(toolkit.loss_history[step] - float(loss)) < 2e-5 * float(loss)
        leaves, treedef = jax.tree.flatten(p)
        out = [ref.adam_step(a, b, c, d, step + 1, 0.0003, 0.0001, warmup=4) for a, b, c, d in zip(
            leaves, *(treedef.flatten_up_to(t) for t in (grads, m, v)))]
        before = p
        p, m, v = (treedef.unflatten([o[i] for o in out]) for i in range(3))
    moved = jax.tree.map(lambda a, b, c: rel(np.asarray(a, np.float64) - c, b - c),
                         toolkit.params, p, before)
    assert max(jax.tree.leaves(moved)) < 2e-2, moved  # a quarter step moves a weight by 4e-3 of itself: float32's grain


def test_the_learn_rate_warms_up_linearly_and_other_trainers_keep_their_step():
    from neutronstarlite_tpu.nn.param import AdamConfig, adam_init, adam_update

    p = {"w": jnp.ones((3,))}
    g = {"w": jnp.full((3,), 0.5)}
    plain, _ = adam_update(p, g, adam_init(p), AdamConfig(alpha=0.01, weight_decay=0.0))
    warm, _ = adam_update(p, g, adam_init(p), AdamConfig(alpha=0.01, weight_decay=0.0, warmup_steps=10))
    assert np.allclose(1.0 - np.asarray(warm["w"]), (1.0 - np.asarray(plain["w"])) / 10.0, rtol=1e-5)
    assert AdamConfig().warmup_steps == 0


def test_the_committed_cfg_parses_and_names_its_model():
    cfg_path = os.path.join(REPO, "configs", "moonlight_16b_a3b_ep8.cfg")
    cfg = InputInfo.read_from_cfg_file(cfg_path)
    with open(cfg.resolve_path(cfg.model_file, os.path.dirname(cfg_path))) as fh:
        spec = seqlm.SeqSpec.from_cfg(json.load(fh), cfg)
    assert (spec.hidden, spec.heads, spec.kv_rank, spec.nope, spec.rope, spec.v_head) == (
        2048, 16, 512, 128, 64, 128)
    assert (spec.ffn, spec.expert_width, spec.shared_width, spec.routed, spec.per_token) == (
        11264, 1408, 2816, 64, 6)
    assert (spec.moe_layers, spec.held, spec.vocab, spec.tokens) == (4, 8, 20480, 32768)
    params = jax.eval_shape(lambda k: seqlm.init_params(k, spec), jax.random.PRNGKey(0))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params)) == 568484352


def test_the_cli_runs_a_cfg(tmp_path):
    from neutronstarlite_tpu import run

    assert run.main([write_cfg(tmp_path, EPOCHS=1)]) == 0


@pytest.mark.parametrize("bad", [64, -1])
def test_an_id_outside_the_slice_is_refused_not_clamped(tmp_path, bad):
    tokens = make_tokens()
    tokens[1, 3] = bad
    with pytest.raises(ValueError, match="leave the vocabulary slice"):
        TokenDatum(tokens, 64)
    with pytest.raises(ValueError, match="leave the vocabulary slice"):
        make_trainer(tmp_path, tokens=tokens)


@pytest.mark.parametrize("key, value, match", [
    ("EXPERT_SHARDS", 5, "must divide"), ("SEQ_LENGTH", 128, "beyond the model"),
    ("SEQ_LAYERS", 1, "at least one expert"), ("ATTN_BLOCK", 5, "does not divide"),
])
def test_a_cut_the_model_does_not_allow_is_refused(tmp_path, key, value, match):
    with pytest.raises(ValueError, match=match):
        make_trainer(tmp_path, **{key: value})


def test_checkpoint_save_and_restore(tmp_path):
    ckpt = tmp_path / "ckpt"
    first = make_trainer(tmp_path, CHECKPOINT_DIR=str(ckpt), EPOCHS=2)
    first.run()
    again = make_trainer(tmp_path, CHECKPOINT_DIR=str(ckpt), EPOCHS=3)
    assert again.restore(str(ckpt)) == 2
    for a, b in zip(jax.tree.leaves(first.checkpoint_state()), jax.tree.leaves(again.checkpoint_state())):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    again.run()  # resumes at epoch 2 and trains the one epoch left
    assert len(again.loss_history) == 1 and int(again.opt_state.step) == 3


def test_a_second_run_trains_on_from_where_the_first_stopped(tmp_path):
    trainer = make_trainer(tmp_path, EPOCHS=2)
    trainer.run()
    trainer.cfg.epochs = 3
    trainer.run()
    assert len(trainer.loss_history) == 5 and int(trainer.opt_state.step) == 5
    one = make_trainer(tmp_path, EPOCHS=5)
    one.run()
    assert one.loss_history == trainer.loss_history  # the corpus kept cycling


# ---- spans, counters, the scope table

def test_spans_and_stages_of_the_funnel_and_the_run_loop(tmp_path):
    from neutronstarlite_tpu.obs.flight import recent_records

    trainer = make_trainer(tmp_path)
    trainer.run()
    spans = recent_records("span")
    names = {r["name"] for r in spans}
    assert {"params_init", "datum_upload", "step_build"} <= {r["name"] for r in spans if r["cat"] == "phase"}
    assert {"run", "epoch", "epoch_key", "step_dispatch", "step_device", "loss_fetch",
            "epoch_emit", "ckpt_epoch_end"} <= names
    epochs = [r for r in spans if r["name"] == "epoch"]
    assert len(epochs) >= 2
    summary = trainer.run_summary_record
    assert summary["epochs"] == 2 and summary["loss_history"] == trainer.loss_history


def test_the_scope_table_covers_every_named_scope(trained):
    trainer, _, _ = trained
    table = trainer.scope_table()
    # every layer of this stack attends: the delta-rule mixer's scopes are tests/test_kda.py's,
    # the short convolution's tests/test_lfm2.py's
    assert set(table.values()) == {s for s in seqlm.SCOPES
                                   if not s.startswith(("seq/kda/", "seq/gqa/", "seq/sconv/"))}
    assert seqlm.scope_of("jit(step)/transpose(jvp(seq/moe/experts))/ragged_dot") == "seq/moe/experts"
    assert seqlm.scope_of("jit(step)/seq/mla/project/seq/mla/attend/while/body/dot") == "seq/mla/attend"
    assert seqlm.scope_of("jit(step)/convert_element_type") is None
    # the same compile gave the step's size, where the backend analyses it
    gauges = trainer.metrics.snapshot(include_hists=False)["gauges"]
    assert gauges["step.generated_code_bytes"] >= 0


def test_bfloat16_compute_stays_near_the_reference(tmp_path):
    trainer = make_trainer(tmp_path, PRECISION="bfloat16")
    p0 = jax.tree.map(np.asarray, trainer.params)
    trainer.run()
    share = ref.Share(trainer.spec.first, trainer.spec.held)
    want, _ = ref.loss(p0, trainer.datum.tokens[:2], SHAPE, share, block=16)
    assert abs(trainer.loss_history[0] - float(want)) < 1e-3 * float(want)
    assert jax.tree.leaves(trainer.params)[0].dtype == jnp.float32  # the masters


# ---- one code path, two dialects of config.json

def _pinned(trainer):
    """(sha256 of the step's lowered text, sha256 of the seeded weights' bytes)."""
    text = trainer._train_step.lower(*trainer.step_args()).as_text()
    weights = hashlib.sha256()
    for leaf in jax.tree.leaves(trainer.params):
        weights.update(np.asarray(leaf).tobytes())
    return hashlib.sha256(text.encode()).hexdigest(), weights.hexdigest()


def test_a_deepseek_v3_file_is_what_it_was(tmp_path):
    """The spec, the parameter tree and the first losses of this file's
    model at seed 3, as the tree before the second dialect gave them; its
    seeded weights byte for byte as the tree before the third dialect gave
    them; its step's lowered text as the step taken part by part left it
    (each part's backward, then its Adam update, a run's routed experts'
    behind an optimization barrier: the text changed by design, the weights
    and the first losses did not)."""
    trainer = make_trainer(tmp_path, EPOCHS=2)
    assert _pinned(trainer) == ("4596c6c0c6c611fde32dd765bc3e437a3898690fd152643291765e0bc7bbcd2a",
                                "afabbe6eff396277eee934423cf61cc5cc1387231b808a9ecc5c882cd8a1e332")
    spec = trainer.spec
    assert (spec.hidden, spec.heads, spec.kv_rank, spec.nope, spec.rope, spec.v_head, spec.ffn,
            spec.expert_width, spec.shared_width, spec.routed, spec.per_token, spec.route_scale,
            spec.theta, spec.eps, spec.moe_layers, spec.first, spec.held, spec.vocab, spec.length,
            spec.batch, spec.block, spec.loss_chunk) == (
        48, 3, 24, 16, 8, 16, 96, 32, 64, 16, 3, 2.446, 50000.0, 1e-05, 2, 4, 4, 64, 32, 2, 8, 16)
    assert spec.mixers == ("mla",) * 3 and spec.rotary and spec.kda_layers == 0
    assert spec.runs == (("moe", "mla", 0, 2),)
    attention = {"norm1", "wq", "wkv_a", "kv_norm", "wkv_b", "wo", "norm2"}
    assert set(trainer.params) == {"embed", "dense", "moe", "norm", "head"}
    assert set(trainer.params["dense"]) == attention | {"wg", "wu", "wd"}
    assert set(trainer.params["moe"]) == attention | {"router", "eg", "eu", "ed", "sg", "su", "sd"}
    assert trainer.params["moe"]["eg"].shape == (2, 4, 48, 32)
    sums = {"embed": 50.702041, "head": 50.029979}
    for name, want in sums.items():
        assert abs(float(jnp.sum(jnp.abs(trainer.params[name]))) - want) < 1e-3
    for name, want in {"wq": 111.541246, "router": 24.085751, "ed": 195.543242}.items():
        assert abs(float(jnp.sum(jnp.abs(trainer.params["moe"][name]))) - want) < 1e-3
    assert abs(float(jnp.sum(jnp.abs(trainer.params["dense"]["wd"]))) - 73.618498) < 1e-3
    trainer.run()
    assert trainer.loss_history == pytest.approx([4.152822971343994, 4.1860880851745605], abs=2e-6)
    assert trainer.metrics.counter_get("kda.token_layers") == 0
    assert (spec.dense_layers, spec.scoring, spec.shared_gate, spec.centred_norms) == (1, "sigmoid", False, False)


def test_a_kimi_linear_file_is_what_it_was(tmp_path):
    """K's twin: the hybrid stack of tests/test_kda.py's model at seed 3:
    its seeded weights and its first losses as the tree before the third
    dialect gave them, byte for byte and to 2e-6; its step's lowered text
    as the step taken part by part left it (the text changed by design,
    the weights and the losses did not)."""
    import test_kda

    trainer = test_kda.make_trainer(tmp_path, EPOCHS=2)
    spec = trainer.spec
    assert spec.mixers == ("kda", "kda", "kda", "mla", "kda") and spec.dense_layers == 1
    assert (spec.kda_heads, spec.kda_value_heads, spec.decay_per_head, spec.gates_low_rank,
            spec.out_gate) == (4, 4, False, True, "sigmoid")
    assert set(trainer.params) == {"embed", "dense", "moe", "moe1", "moe2", "norm", "head"}
    assert _pinned(trainer) == ("b99cf1b096a7a94c6298cdf7fc00a7caf2e926e34922426f7f26c025e13ae789",
                                "4290d11384bbbee69bde339c79da5b15038edd1de46fb7a3214c62b5a3f9b1d1")
    trainer.run()
    assert trainer.loss_history == pytest.approx([4.184228420257568, 4.158705711364746], abs=2e-6)
    assert trainer.metrics.counter_get("gqa.token_layers") == 0


def test_a_qwen3_next_file_is_what_it_was(tmp_path):
    """Q's twin: the stack of tests/test_gdn.py's model at seed 3 (three
    delta-rule layers, one gated attention layer, no dense layer): its
    seeded weights and its first losses as they were before the dialects
    became records; its step's lowered text as the step taken part by part
    left it (the text changed by design, the weights and the losses did
    not)."""
    import test_gdn

    trainer = test_gdn.make_trainer(tmp_path, EPOCHS=2)
    spec = trainer.spec
    assert spec.mixers == ("kda", "kda", "kda", "gqa") and spec.dense_layers == 0
    assert spec.runs == (("moe", "kda", 0, 3), ("moe1", "gqa", 3, 1))
    assert set(trainer.params) == {"embed", "moe", "moe1", "norm", "head"}
    assert _pinned(trainer) == ("cfc41f0ac76e2cecd0221f730a9c94160818735fa8b9f415f62048589454bf71",
                                "36b9908258e568d8aa4f1de3e6fbb01ad32cdd81dd2daec1d16e7aa3f79557d3")
    trainer.run()
    assert trainer.loss_history == pytest.approx([4.16268253326416, 4.1641926765441895], abs=2e-6)
    assert trainer.metrics.counter_get("gqa.token_layers") == 128
    assert trainer.metrics.counter_get("kda.token_layers") == 384


@pytest.mark.parametrize("family", ["test_gdn", "test_lfm2"])
def test_the_step_part_by_part_is_the_step_whole(tmp_path, family):
    """The step that takes the backward part by part, each run's routed
    experts updated behind an optimization barrier, gives the loss, the
    weights and Adam's moments of one ``value_and_grad`` over the whole loss
    followed by one Adam update, for stacks with two leading dense layers or
    none, and two runs; one barrier a run. (This file's own stack, one dense
    layer and one run, has its step's gradients against the reference.)"""
    import importlib

    from neutronstarlite_tpu.nn.param import adam_update

    trainer = importlib.import_module(family).make_trainer(tmp_path)
    spec = trainer.spec

    def whole(params, opt, bias, corpus, index):
        (loss, _), grads = jax.value_and_grad(trainer._loss, has_aux=True)(params, bias, corpus[index])
        return (*adam_update(params, grads, opt, trainer.adam_cfg), loss)

    lowered = [jax.jit(step).lower(*trainer.step_args()) for step in (trainer._step, whole)]
    got, want = (step.compile()(*trainer.initial_state(), *trainer.step_args()[2:]) for step in lowered)
    assert float(got[2]) == pytest.approx(float(want[2]), rel=1e-6)
    assert int(got[1].step) == int(want[1].step) == 1
    for name, a, b in ((n, a, b) for n, tree_a, tree_b in zip(("params", "m", "v"), (
            got[0], got[1].m, got[1].v), (want[0], want[1].m, want[1].v))
            for a, b in zip(jax.tree.leaves(tree_a), jax.tree.leaves(tree_b))):
        assert a.shape == b.shape and rel(a, b) < 1e-6, name
    # the whole step's barriers are jax.checkpoint's own
    barriers = [step.as_text().count("= stablehlo.optimization_barrier") for step in lowered]
    assert barriers[0] - barriers[1] == len(spec.runs)


@pytest.mark.parametrize("key, value", [
    ("q_lora_rank", 1536), ("n_group", 8), ("topk_group", 4), ("scoring_func", "softmax"),
    ("norm_topk_prob", False), ("moe_layer_freq", 2),
])
def test_a_deepseek_v3_key_the_family_does_not_compute_is_refused_by_name(key, value):
    cfg = InputInfo()
    cfg.seq_layers, cfg.seq_length = 3, 32
    with pytest.raises(ValueError, match=f"MODEL_FILE has {key}="):
        seqlm.SeqSpec.from_cfg(dict(MODEL, **{key: value}), cfg)


def test_a_deepseek_v3_file_with_three_dense_layers_leads_with_three(tmp_path, monkeypatch):
    """``first_k_dense_replace`` is the count of leading dense layers, each
    under its own key, every one trained (PR 39: no dialect refuses more
    than one)."""
    spec = seqlm.SeqSpec.from_cfg(dict(MODEL, first_k_dense_replace=3), _spec_cfg(seq_layers=5))
    assert (spec.dense_layers, spec.moe_layers, spec.runs) == (3, 2, (("moe", "mla", 0, 2),))
    with pytest.raises(ValueError, match="SEQ_LAYERS:3 must keep the 3 dense layers and"):
        seqlm.SeqSpec.from_cfg(dict(MODEL, first_k_dense_replace=3), _spec_cfg(seq_layers=3))
    monkeypatch.setitem(MODEL, "first_k_dense_replace", 3)
    trainer = make_trainer(tmp_path, SEQ_LAYERS=5)
    assert set(trainer.params) == {"embed", "dense", "dense1", "dense2", "moe", "norm", "head"}
    before = jax.tree.map(np.asarray, trainer.params)
    trainer.run()
    assert np.all(np.isfinite(trainer.loss_history)) and trainer.route_bias.shape == (2, 16)
    for key in ("dense", "dense1", "dense2"):
        assert not np.array_equal(before[key]["wd"], np.asarray(trainer.params[key]["wd"]))


def _spec_cfg(**keys):
    cfg = InputInfo()
    cfg.seq_length = 32
    for k, v in keys.items():
        setattr(cfg, k, v)
    return cfg


# ---- the hybrid stack (a kimi_linear file) against its reference

@pytest.fixture(scope="module")
def hybrid(tmp_path_factory):
    """A float32 trainer over dense-KDA, KDA, KDA, latent attention, KDA
    (tests/test_kda.py's model), its reference, its initial weights."""
    import test_kda

    trainer = test_kda.make_trainer(tmp_path_factory.mktemp("hybrid"))
    return trainer, test_kda.ref, test_kda.SHAPE, jax.tree.map(np.asarray, trainer.params)


def _by_sequence(choice, sequences):
    c = np.asarray(choice)
    return c.reshape(c.shape[0], sequences, -1, c.shape[-1]).transpose(1, 0, 2, 3)


def test_the_hybrid_trainer_matches_the_reference(hybrid):
    """Logits, loss, every gradient leaf of every layer (both mixers, both
    MLPs, embedding, head), and the tail's gradients as the chip's check
    computes them."""
    trainer, kref, shape, p0 = hybrid
    assert trainer.spec.mixers == ("kda", "kda", "kda", "mla", "kda")
    batch = trainer.datum.tokens[:2]
    share = kref.Share(trainer.spec.first, trainer.spec.held)
    logits, choice = trainer._eval_logits(
        trainer.initial_state()[0], trainer.route_bias, jnp.asarray(batch), jnp.arange(64))
    want_loss, own = kref.loss(p0, batch, shape, share, block=16)
    assert np.array_equal(np.sort(_by_sequence(choice, 2), -1), np.sort(np.asarray(own), -1))
    want = np.concatenate([np.asarray(kref.head_logits(
        p0, kref.hidden_states(p0, batch[s], shape, share, block=16)[0], shape)) for s in range(2)])
    assert np.abs(np.asarray(logits) - want).max() / np.abs(want).max() < 1e-5
    loss, grads = jax.value_and_grad(
        lambda p: trainer._loss(p, trainer.route_bias, jnp.asarray(batch))[0])(trainer.initial_state()[0])
    ref_loss, ref_grads = kref.loss_and_grads(p0, batch, shape, share)
    assert abs(float(loss) - float(want_loss)) < 1e-5 * float(want_loss)
    assert abs(float(ref_loss) - float(want_loss)) < 1e-5 * float(want_loss)
    assert jax.tree.structure(grads) == jax.tree.structure(ref_grads)
    errors = jax.tree.map(rel, grads, ref_grads)
    assert max(jax.tree.leaves(errors)) < 2e-4, errors
    tail_loss, tail = kref.tail_loss_and_grads(p0, batch, shape, share, block=16)
    assert abs(float(tail_loss) - float(ref_loss)) < 1e-5
    assert [("wkv_a" in lp) for lp in tail["layers"]] == [True, False]  # one of each mixer
    assert max(jax.tree.leaves(jax.tree.map(rel, tail, kref.tail_of(ref_grads)))) < 2e-4


def test_two_adam_steps_of_the_hybrid_trainer_follow_the_reference(hybrid):
    trainer, kref, shape, p0 = hybrid
    share = kref.Share(trainer.spec.first, trainer.spec.held)
    params, opt = trainer.initial_state()
    want = jax.tree.map(lambda a: np.asarray(a, np.float64), p0)
    m = v = jax.tree.map(np.zeros_like, want)
    for step in range(2):
        batch = trainer.datum.tokens[2 * step: 2 * step + 2]
        _, grads = kref.loss_and_grads(want, batch, shape, share)
        params, opt, _, _, _ = trainer._train_step(params, opt, trainer.route_bias, trainer.corpus,
                                                   trainer._batch_index[step])
        leaves, tree = jax.tree.flatten(want)
        out = [kref.adam_step(p, g, a, b, step + 1, 0.0003, 0.0001)
               for p, g, a, b in zip(leaves, tree.flatten_up_to(grads), tree.flatten_up_to(m),
                                     tree.flatten_up_to(v))]
        want, m, v = (tree.unflatten([o[i] for o in out]) for i in range(3))
        moved = jax.tree.map(lambda a, b, c: rel(np.asarray(a, np.float64) - c, b - c), params, want, p0)
        assert max(jax.tree.leaves(moved)) < 5e-3, (step, moved)
