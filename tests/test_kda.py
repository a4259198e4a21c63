"""The delta-rule mixer of the token-sequence family (ops/delta_rule.py,
nn/seq.py's convolution and norms, models/seqlm.py's second dialect) against
the plain reference the benchmark compares with
(benchmark/reference/kimi_linear.py, loaded by its path: one reference, no
second copy), on the CPU, float32, small widths, seeded weights."""

import dataclasses
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neutronstarlite_tpu.models import seqlm
from neutronstarlite_tpu.nn import seq as nnseq
from neutronstarlite_tpu.nn.layers import compute_cast
from neutronstarlite_tpu.ops import causal_attention, conv_operand, delta_rule
from neutronstarlite_tpu.utils.config import InputInfo

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_reference():
    path = os.path.join(REPO, "benchmark", "reference", "kimi_linear.py")
    spec = importlib.util.spec_from_file_location("reference_kimi_linear", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _load_reference()

# the keys of a kimi_linear config.json, small: eight layers, 3 KDA to 1 latent attention
MODEL = dict(
    model_type="kimi_linear", hidden_size=48, num_attention_heads=3, kv_lora_rank=24,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, intermediate_size=96,
    moe_intermediate_size=32, num_shared_experts=1, num_experts=16, num_experts_per_token=3,
    routed_scaling_factor=2.446, rope_theta=10000, rms_norm_eps=1e-5, num_hidden_layers=8,
    vocab_size=128, model_max_length=64, q_lora_rank=None, num_expert_group=1, topk_group=1,
    moe_router_activation_func="sigmoid", moe_renormalize=True, first_k_dense_replace=1,
    moe_layer_freq=1, hidden_act="silu", mla_use_nope=True, use_grouped_topk=True,
    linear_attn_config=dict(kda_layers=[1, 2, 3, 5, 6, 7], full_attn_layers=[4, 8], num_heads=4,
                            head_dim=8, short_conv_kernel_size=4),
)
SHAPE = ref.Shape.of(MODEL)
CUT = dict(SEQ_LAYERS=5, SEQ_LENGTH=32, SEQ_BATCH=2, SEQ_CORPUS=3, EXPERT_SHARDS=4,
           EXPERT_SHARD=1, VOCAB_SHARDS=2, ATTN_BLOCK=8, LOSS_CHUNK=16, KDA_CHUNK=8, EPOCHS=2,
           LEARN_RATE=0.0003, WEIGHT_DECAY=0.0001, DECAY_EPOCH=-1)


def make_trainer(tmp_path, model=MODEL, seed=3, **keys):
    """A SEQLM trainer from a cfg file beside its model JSON, as a user's."""
    with open(tmp_path / "model.json", "w") as fh:
        json.dump(model, fh)
    path = tmp_path / "seq.cfg"
    with open(path, "w") as fh:
        fh.writelines(f"{k}:{v}\n" for k, v in
                      dict(CUT, ALGORITHM="SEQLM", MODEL_FILE="model.json", **keys).items())
    tokens = np.random.default_rng(0).integers(0, 64, size=(6, 32), dtype=np.int32)
    return seqlm.SeqLMTrainer.from_tokens(InputInfo.read_from_cfg_file(str(path)), tokens,
                                          seed=seed, base_dir=str(tmp_path))


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


# ---- the chunked delta rule against the recurrence, position by position

def _recurrence_inputs(rng, rows, positions, dk, dv, gate):
    unit = lambda t: t / np.linalg.norm(t, axis=-1, keepdims=True)  # noqa: E731
    q = unit(rng.standard_normal((rows, positions, dk))).astype(np.float32)
    k = unit(rng.standard_normal((rows, positions, dk))).astype(np.float32)
    v = rng.standard_normal((rows, positions, dv)).astype(np.float32)
    g = -rng.uniform(0.0, gate, (rows, positions, dk)).astype(np.float32)
    beta = rng.uniform(0.0, 1.0, (rows, positions)).astype(np.float32)
    return tuple(jnp.asarray(t) for t in (q, k, v, g, beta))


def _by_tokens(q, k, v, g, beta):
    """The reference's recurrence over rows ``[N, S, .]`` (it takes ``[T, H, .]``)."""
    t = lambda a: jnp.swapaxes(a, 0, 1)  # noqa: E731
    return t(ref.delta_rule_tokens(t(q), t(k), t(v), t(g), t(beta), segment=16))


@pytest.mark.parametrize("chunk", [64, 32, 16, 4])
@pytest.mark.parametrize("gate", [0.1, 3.0, 60.0])
def test_the_chunked_delta_rule_is_the_recurrence(rng, chunk, gate):
    """Outputs and every gradient, float32. At ``gate`` 60 a chunk's
    cumulative decay is ``exp(-2000)``: it underflows, a quotient of two of
    them would be 0/0, and the chunked form stays finite and equal."""
    args = _recurrence_inputs(rng, 3, 128, 8, 6, gate)
    weight = jnp.asarray(rng.standard_normal((3, 128, 6)).astype(np.float32))
    with jax.default_matmul_precision("highest"):
        got = delta_rule.gated_delta_rule(*args, chunk=chunk)
        want = _by_tokens(*args)
        d_got = jax.grad(lambda *a: jnp.sum(delta_rule.gated_delta_rule(*a, chunk=chunk) * weight),
                         argnums=range(5))(*args)
        d_want = jax.grad(lambda *a: jnp.sum(_by_tokens(*a) * weight), argnums=range(5))(*args)
    assert np.all(np.isfinite(np.asarray(got))) and rel(got, want) < 1e-5
    for name, a, b in zip("q k v g beta".split(), d_got, d_want):
        assert np.all(np.isfinite(np.asarray(a))), name
        assert rel(a, b) < 5e-5, name


def test_the_state_is_carried_across_chunks(rng):
    """What a later chunk gives depends on the earlier ones: cutting the
    sequence in two and starting again from zero is another result."""
    q, k, v, g, beta = _recurrence_inputs(rng, 2, 32, 8, 8, 0.05)
    whole = delta_rule.gated_delta_rule(q, k, v, g, beta, chunk=16)
    second = delta_rule.gated_delta_rule(q[:, 16:], k[:, 16:], v[:, 16:], g[:, 16:], beta[:, 16:], chunk=16)
    assert rel(whole[:, :16], delta_rule.gated_delta_rule(
        q[:, :16], k[:, :16], v[:, :16], g[:, :16], beta[:, :16], chunk=16)) < 1e-6
    assert rel(whole[:, 16:], second) > 0.1


def test_the_inverse_of_a_unit_lower_triangle(rng):
    lower = np.tril(rng.standard_normal((5, 64, 64)), -1).astype(np.float32) * 0.3
    with jax.default_matmul_precision("highest"):
        got = delta_rule.unit_lower_inverse(jnp.asarray(lower))
    want = np.linalg.inv(np.eye(64) + lower.astype(np.float64))
    assert rel(got, want) < 1e-5


# ---- the chunk walk as one kernel (interpret mode here) against the scan

def walk_inputs(rng, key_rows, each, positions, dk, dv, gate, per_head, dtype):
    """The walks' operands: unit q and k, a cumulative log-decay per
    channel ``[N, S, dk]`` or per head ``[N, S]`` over chunks of 16."""
    rows = key_rows * each
    unit = lambda t: t / np.linalg.norm(t, axis=-1, keepdims=True)  # noqa: E731
    q, k = (jnp.asarray(unit(rng.standard_normal((key_rows, positions, dk))), dtype) for _ in "qk")
    v = jnp.asarray(rng.standard_normal((rows, positions, dv)), dtype)
    g = -rng.uniform(0.0, gate, (rows, positions, 1 if per_head else dk)).astype(np.float32)
    decay = delta_rule.chunk_log_decay(jnp.asarray(g), 16).reshape(rows, positions, *([] if per_head else [dk]))
    beta = jnp.asarray(rng.uniform(0.0, 1.0, (rows, positions)).astype(np.float32))
    return q, k, v, decay, beta


def the_kernel_is_the_scan(rng, per_head, each, gate, dtype, limit, positions=32):
    """Outputs and all five gradients of the kernels (Pallas' interpret
    mode) against the scan's, two row blocks of eight value rows."""
    cast = compute_cast("bfloat16" if dtype == jnp.bfloat16 else None)
    args = walk_inputs(rng, 16 // each, each, positions, 8, 8, gate, per_head, dtype)
    weight = jnp.asarray(rng.standard_normal(args[2].shape).astype(np.float32))

    def both(form):
        out, pull = jax.vjp(lambda *a: delta_rule.recurrence(*a, cast, 16, form), *args)
        return out, pull(weight.astype(out.dtype))

    (got, d_got), (want, d_want) = both("interpret"), both("scan")
    assert got.dtype == dtype and np.all(np.isfinite(np.asarray(got, np.float32)))
    assert rel(got, want) < limit
    for name, a, b in zip("q k v decay beta".split(), d_got, d_want):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        assert np.all(np.isfinite(np.asarray(a, np.float32))) and rel(a, b) < limit, name


@pytest.mark.parametrize("dtype, limit", [(jnp.float32, 1e-5), (jnp.bfloat16, 1e-2)])
@pytest.mark.parametrize("gate", [0.1, 3.0, 60.0])
@pytest.mark.parametrize("each", [1, 2])
def test_the_kernel_is_the_scan_with_a_decay_per_channel(rng, each, gate, dtype, limit):
    the_kernel_is_the_scan(rng, False, each, gate, dtype, limit)


def test_the_kernel_carries_the_state_across_grid_steps_and_zeroes_it_for_a_row_block(rng):
    """512 positions are two grid steps of four chunks of 64; 16 rows are two
    row blocks: the second starts from zero, whatever the first left in
    the scratch (what it gives is what it gives alone), and the backward
    kernel's cotangent of the state likewise."""
    cast = compute_cast(None)
    q, k, v, decay, beta = walk_inputs(rng, 16, 1, 512, 8, 8, 0.05, False, jnp.float32)
    decay = delta_rule.chunk_log_decay(jnp.diff(decay, axis=1, prepend=0.0), 64).reshape(decay.shape)
    weight = jnp.asarray(rng.standard_normal(v.shape).astype(np.float32))

    def both(form, rows=slice(None)):
        args = tuple(t[rows] for t in (q, k, v, decay, beta))
        out, pull = jax.vjp(lambda *a: delta_rule.recurrence(*a, cast, 64, form), *args)
        return out, pull(weight[rows])

    (got, d_got), (want, d_want) = both("interpret"), both("scan")
    assert rel(got, want) < 1e-5 and rel(got[:, 256:], want[:, 256:]) < 1e-5
    assert all(rel(a, b) < 1e-5 for a, b in zip(d_got, d_want))
    alone, d_alone = both("interpret", slice(8, 16))
    assert np.array_equal(np.asarray(got[8:]), np.asarray(alone))
    assert all(np.array_equal(np.asarray(a[8:]), np.asarray(b)) for a, b in zip(d_got, d_alone))
    # the later steps do read the state: from zero they give another result
    fresh, _ = jax.vjp(lambda *a: delta_rule.recurrence(*a, cast, 64, "interpret"),
                       *(t[:, 256:] for t in (q, k, v, decay, beta)))
    assert rel(fresh, got[:, 256:]) > 0.05


def test_the_sizes_the_kernels_take():
    takes = delta_rule.kernel_takes
    assert takes(64, 64, 8192, 128, 128, 64) and takes(32, 64, 8192, 128, 128, 64)
    assert not takes(64, 64, 8192, 64, 128, 64)  # a key head of 64: the scan
    assert not takes(64, 64, 8192, 128, 128, 8)  # a chunk that is no multiple of SUB_BLOCK
    assert not takes(6, 12, 8192, 128, 128, 64)  # 12 rows are no whole blocks of 8
    assert not takes(4, 64, 8192, 128, 128, 64)  # 16 value heads a key head: a block of 8 holds half a key head
    assert takes(64, 64, 192, 128, 128, 64) and not takes(64, 64, 200, 128, 128, 64)  # whole chunks
    assert takes(2, 4, 64, 128, 128, 64) and delta_rule.rows_per_block(4) == 4


# ---- the convolution and the norms

def _conv_by_hand(x, w):
    out = np.zeros_like(x, dtype=np.float64)
    taps = w.shape[1]
    for b in range(x.shape[0]):
        for t in range(x.shape[1]):
            for i in range(taps):
                at = t - (taps - 1) + i
                if at >= 0:
                    out[b, t] += w[:, i] * x[b, at]
    return out


@pytest.mark.parametrize("taps", [1, 2, 4])
def test_the_causal_convolution_is_the_explicit_sum(rng, taps):
    x = rng.standard_normal((3, 12, 5)).astype(np.float32)
    w = rng.standard_normal((5, taps)).astype(np.float32)
    assert rel(nnseq.causal_conv(jnp.asarray(x), jnp.asarray(w)), _conv_by_hand(x, w)) < 1e-6
    flat = x.reshape(36, 5)  # the reference's, one sequence at a time
    for b in range(3):
        assert rel(ref.short_conv(jnp.asarray(flat[12 * b: 12 * b + 12]), jnp.asarray(w)),
                   _conv_by_hand(x, w)[b]) < 1e-6


def test_the_convolution_reads_no_later_position_and_no_other_sequence(rng):
    x = rng.standard_normal((2, 10, 4)).astype(np.float32)
    w = jnp.asarray(rng.standard_normal((4, 4)).astype(np.float32))
    base = np.asarray(nnseq.causal_conv(jnp.asarray(x), w))
    later = x.copy()
    later[0, 6] += 1.0
    moved = np.asarray(nnseq.causal_conv(jnp.asarray(later), w))
    assert np.array_equal(moved[0, :6], base[0, :6]) and not np.array_equal(moved[0, 6:], base[0, 6:])
    assert np.array_equal(moved[1], base[1])  # the other sequence of the batch
    last = x.copy()
    last[0, 9] += 1.0  # the last position of sequence 0 sits before the first of sequence 1
    assert np.array_equal(np.asarray(nnseq.causal_conv(jnp.asarray(last), w))[1], base[1])


def test_the_l2_norm_and_the_gated_rms_norm(rng):
    x = rng.standard_normal((7, 3, 8)).astype(np.float32)
    gate = rng.standard_normal((7, 3, 8)).astype(np.float32)
    w = rng.standard_normal(8).astype(np.float32)
    assert rel(nnseq.l2_norm(jnp.asarray(x)), x / np.sqrt((x * x).sum(-1, keepdims=True) + 1e-6)) < 1e-6
    want = x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-5) * w / (1.0 + np.exp(-gate))
    assert rel(nnseq.gated_rms_norm(jnp.asarray(x), jnp.asarray(w), jnp.asarray(gate), 1e-5), want) < 1e-6


# ---- one pass from the product to the recurrence's operand (ops/conv_operand.py)

def _operand_by_formula(x, taps, scale, heads):
    """What the pass replaces, written out: the explicit tap sum, SiLU,
    ``x / |x|`` per head, the scale, the cast, rows by head."""
    b, s, wide = x.shape
    k, d = taps.shape[1], wide // heads
    x32 = x.astype(jnp.float32)
    y = sum(taps[:, i] * jnp.concatenate(
        [jnp.zeros((b, k - 1 - i, wide), jnp.float32), x32[:, : s - (k - 1 - i)]], axis=1)
        for i in range(k))
    t = (y / (1.0 + jnp.exp(-y))).reshape(b, s, heads, d)
    if scale is not None:
        t = t / jnp.sqrt(jnp.sum(t * t, axis=-1, keepdims=True) + 1e-6) * scale
    return jnp.swapaxes(t.astype(x.dtype), 1, 2).reshape(b * heads, s, d)


@pytest.mark.parametrize("dtype, limit", [(jnp.float32, 1e-6), (jnp.bfloat16, 1e-3)])
@pytest.mark.parametrize("taps", [1, 2, 4])
@pytest.mark.parametrize("form", ["q", "k", "v"])
def test_the_operand_pass_is_the_formula_it_replaces(rng, form, taps, dtype, limit):
    """Forward, and the gradients of the product and of the taps against
    autodiff of the formula; three tiles of 16 positions, so that a tile
    reads the one before it and its cotangent the one after."""
    b, s, h, d = 2, 48, 3, 8
    scale = {"q": d ** -0.5, "k": 1.0, "v": None}[form]
    x = jnp.asarray(rng.standard_normal((b, s, h * d)), dtype)
    w = jnp.asarray(rng.standard_normal((h * d, taps)).astype(np.float32))
    weight = jnp.asarray(rng.standard_normal((b * h, s, d)).astype(np.float32))
    got = conv_operand.conv_operand(x, w, scale, h, 16)
    assert got.dtype == dtype and got.shape == (b * h, s, d)
    assert rel(got, _operand_by_formula(x, w, scale, h)) < limit

    def grads(f):
        return jax.grad(lambda x, w: jnp.sum(f(x, w).astype(jnp.float32) * weight), argnums=(0, 1))(x, w)

    d_got = grads(lambda x, w: conv_operand.conv_operand(x, w, scale, h, 16))
    d_want = grads(lambda x, w: _operand_by_formula(x, w, scale, h))
    assert d_got[0].dtype == dtype and d_got[1].dtype == jnp.float32
    for name, a, b_ in zip(("product", "taps"), d_got, d_want):
        assert rel(a, b_) < limit, name
    # one tile or three: the same operand
    assert rel(conv_operand.conv_operand(x, w, scale, h), got) < limit


def test_an_operand_row_reads_no_later_position_no_other_head_and_no_other_sequence(rng):
    """Row ``b * H + h`` is sequence ``b``'s head ``h``: it reads nothing
    of row ``b * H + h - 1`` (another head, or the sequence before), and
    nothing later than itself, across a tile's edge too."""
    b, s, h, d = 2, 32, 3, 8
    x = rng.standard_normal((b, s, h * d)).astype(np.float32)
    w = jnp.asarray(rng.standard_normal((h * d, 4)).astype(np.float32))
    run = lambda a: np.asarray(conv_operand.conv_operand(jnp.asarray(a), w, 1.0, h, 16))  # noqa: E731
    base = run(x)
    for at in (6, 15, 31):  # inside a tile, a tile's last position, the sequence's last
        moved_x = x.copy()
        moved_x[0, at, d: 2 * d] += 1.0  # sequence 0, head 1
        moved = run(moved_x)
        assert np.array_equal(moved[1, :at], base[1, :at])
        assert not np.array_equal(moved[1, at: at + 4], base[1, at: at + 4])
        assert np.array_equal(moved[1, at + 4:], base[1, at + 4:])  # four taps reach three back
        for row in (0, 2, 3, 4, 5):  # its sequence's other heads; the other sequence
            assert np.array_equal(moved[row], base[row]), (at, row)
    # and the gradient of a row's loss reaches its own channels of its own sequence alone
    dx = np.asarray(jax.grad(lambda a: jnp.sum(conv_operand.conv_operand(a, w, 1.0, h, 16)[4]))(
        jnp.asarray(x)))
    assert np.any(dx[1, :, d: 2 * d]) and not np.any(dx[0]) and not np.any(dx[1, :, :d])
    assert not np.any(dx[1, :, 2 * d:])


def test_the_operand_pass_takes_whole_tiles():
    assert conv_operand.tile_of(8192) == 1024 and conv_operand.tile_of(48) == 48
    assert conv_operand.tile_of(48, 16) == 16 and conv_operand.tile_of(1040) == 208
    with pytest.raises(ValueError, match="a sequence of 40 is no multiple"):
        conv_operand.tile_of(40)
    with pytest.raises(ValueError, match="a convolution of 18 taps reads further back"):
        conv_operand.conv_operand(jnp.zeros((1, 32, 8)), jnp.zeros((8, 18)), None, 1)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("scale", [1.0, None])
def test_the_operand_kernels_compile_for_the_chip(one_chip, scale):
    """Mosaic takes both kernels at the published head width and the
    default tile (a compile for a described v5e: nothing runs)."""
    b, s, h, d = 1, 2 * conv_operand.TILE, 2, 128
    x = jax.ShapeDtypeStruct((b, s, h * d), jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((h * d, 4), jnp.float32, sharding=one_chip)
    g = jax.ShapeDtypeStruct((b * h, s, d), jnp.bfloat16, sharding=one_chip)

    def step(x, w, g):
        out, pull = jax.vjp(lambda x, w: conv_operand.conv_operand(x, w, scale, h), x, w)
        return out, pull(g)

    text = jax.jit(step).lower(x, w, g).compile().as_text()
    assert text.count("tpu_custom_call") >= 2
    assert "kda_conv_operand" in text and "kda_conv_operand_backward" in text


@pytest.mark.parametrize("per_head, each", [(False, 1), (True, 2)])
def test_the_recurrence_kernels_compile_for_the_chip_under_their_scope(one_chip, per_head, each):
    """Mosaic takes both kernels at a layer's sizes in the benchmark's two
    cells (64 rows of v, heads of 128, chunks of 64; a decay per channel,
    and a decay per head with two value heads a key head), and the step's
    scope table finds both custom calls under the recurrence's scope (a
    compile for a described v5e: nothing runs)."""
    rows, s, d = 64, 256, 128
    cast = compute_cast("bfloat16")
    shape = lambda *dims, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)  # noqa: E731
    q, v = shape(rows // each, s, d), shape(rows, s, d)
    log_decay = shape(rows, s // 64, 64, 1 if per_head else d, dtype=jnp.float32)
    assert delta_rule.kernel_takes(rows // each, rows, s, d, d, 64)

    def step(q, k, v, log_decay, beta, g):
        def recur(*a):
            with jax.named_scope("seq/kda/recur"):
                return delta_rule.recurrence(
                    *a[:3], a[3].reshape(rows, s, *([] if per_head else [d])), a[4], cast, 64, "kernel")
        out, pull = jax.vjp(recur, q, k, v, log_decay, beta)
        return out, pull(g)

    text = jax.jit(step).lower(q, q, v, log_decay, shape(rows, s, dtype=jnp.float32), v).compile().as_text()
    table = seqlm.scope_table_of(text)
    calls = {name: scope for name, scope in table.items() if name.startswith("kda_recurrence")}
    assert any(n.startswith("kda_recurrence_backward") for n in calls), sorted(table)[:40]
    assert any(not n.startswith("kda_recurrence_backward") for n in calls)
    assert set(calls.values()) == {"seq/kda/recur"}


@pytest.mark.parametrize("scope, n, group, dk, dv", [
    ("seq/mla/attend", 4, 1, 192, 128), ("seq/gqa/attend", 2, 4, 64, 64), ("seq/gqa/attend", 1, 8, 256, 256)])
def test_the_attention_kernels_compile_for_the_chip_under_their_scope(one_chip, scope, n, group, dk, dv):
    """Mosaic takes the attention's forward and backward kernels at M's, L's
    and Q's head widths and the default tile, chosen by the public call as the
    program is lowered for the chip, and the step's scope table finds both
    custom calls under the attention's scope (a compile for a described
    v5e: nothing runs)."""
    s = 2 * causal_attention.DEFAULT_BLOCK
    shape = lambda *dims: jax.ShapeDtypeStruct(dims, jnp.bfloat16, sharding=one_chip)  # noqa: E731

    def step(q, k, v, g):
        def attend(*a):
            with jax.named_scope(scope):
                return causal_attention.causal_edge_attention(*a, dk ** -0.5, 0, group)
        out, pull = jax.vjp(attend, q, k, v)
        return out, pull(g)

    text = jax.jit(step).lower(shape(n * group, s, dk), shape(n, s, dk), shape(n, s, dv),
                               shape(n * group, s, dv)).compile().as_text()
    table = seqlm.scope_table_of(text)
    calls = {name: scope for name, scope in table.items() if "causal_attention" in name}
    assert any("causal_attention_backward" in name for name in calls), sorted(table)[:40]
    assert any("causal_attention_backward" not in name for name in calls)
    assert set(calls.values()) == {scope}


# ---- the mixer against the reference's, one layer

@pytest.fixture(scope="module")
def trainer(tmp_path_factory):
    """The file's model as ``make_trainer`` builds it, for the tests that
    only read it (a test that runs a trainer builds its own)."""
    return make_trainer(tmp_path_factory.mktemp("kda"))


def test_the_delta_mixer_is_the_reference_mixer(trainer, rng):
    lp = jax.tree.map(np.asarray, trainer.params["dense"])
    x = rng.standard_normal((64, SHAPE.hidden)).astype(np.float32)
    got = seqlm.delta_attention(lp, jnp.asarray(x), trainer.spec, compute_cast(None), jnp.float32)
    want = np.concatenate([np.asarray(ref.kda_mixer(lp, jnp.asarray(x[lo: lo + 32]), SHAPE))
                           for lo in (0, 32)])
    assert rel(got, want) < 1e-5
    # a sequence's rows do not depend on the other sequence of the batch
    other = x.copy()
    other[:32] += 1.0
    moved = seqlm.delta_attention(lp, jnp.asarray(other), trainer.spec, compute_cast(None), jnp.float32)
    assert np.array_equal(np.asarray(moved)[32:], np.asarray(got)[32:])


def test_the_latent_attention_turns_nothing_without_positions(trainer, rng):
    assert trainer.spec.rotary is False
    lp = jax.tree.map(lambda a: np.asarray(a[0]), trainer.params["moe1"])
    x = rng.standard_normal((64, SHAPE.hidden)).astype(np.float32)
    got = seqlm.attention(lp, jnp.asarray(x), trainer.spec, compute_cast(None), jnp.float32)
    want = np.concatenate([np.asarray(ref.mixer(lp, jnp.asarray(x[lo: lo + 32]), SHAPE))
                           for lo in (0, 32)])
    assert rel(got, want) < 1e-5
    turned = seqlm.attention(lp, jnp.asarray(x), dataclasses.replace(trainer.spec, rotary=True),
                             compute_cast(None), jnp.float32)
    assert rel(np.asarray(turned) - x, want - x) > 2e-3  # the layer's own part, without the stream


# ---- the share, tied to the model

def test_the_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer(rng):
    d, w = SHAPE.hidden, MODEL["moe_intermediate_size"]
    n = lambda *s: (rng.standard_normal(s) * 0.3).astype(np.float32)  # noqa: E731
    lp = {"norm2": np.ones(d, np.float32), "router": n(d, 16), "eg": n(16, d, w), "eu": n(16, d, w),
          "ed": n(16, w, d), "sg": n(d, w), "su": n(d, w), "sd": n(w, d)}
    x = rng.standard_normal((40, d)).astype(np.float32)
    bias = jnp.zeros((16,), jnp.float32)
    whole, _ = ref.expert_mlp(lp, jnp.asarray(x), bias, SHAPE, ref.Share(0, 16))
    cast = compute_cast(None)
    shared = np.asarray(nnseq.swiglu(nnseq.rms_norm(x, lp["norm2"], SHAPE.eps),
                                     lp["sg"], lp["su"], lp["sd"], cast))
    total, rows = x + shared, 0
    for shard in range(4):
        cfg = InputInfo()
        cfg.seq_layers, cfg.seq_length, cfg.seq_batch = 2, 40, 1
        cfg.expert_shards, cfg.expert_shard, cfg.kda_chunk = 4, shard, 8
        spec = seqlm.SeqSpec.from_cfg(MODEL, cfg)
        mine = dict(lp, **{k: lp[k][4 * shard: 4 * shard + 4] for k in ("eg", "eu", "ed")})
        out, sizes, _ = seqlm.expert_mlp(mine, bias, jnp.asarray(x), spec, cast)
        total = total + (np.asarray(out) - x - shared)
        rows += int(np.asarray(sizes).sum())
    assert rows == 40 * 3  # every pair was computed by exactly one share
    assert rel(total, whole) < 1e-5


# ---- the two dialects of config.json

def _cfg(**keys):
    cfg = InputInfo()
    cfg.seq_layers, cfg.seq_length, cfg.seq_batch, cfg.kda_chunk = 5, 32, 2, 8
    for k, v in keys.items():
        setattr(cfg, k, v)
    return cfg


def test_a_kimi_linear_file_names_the_mixer_of_every_layer():
    spec = seqlm.SeqSpec.from_cfg(MODEL, _cfg())
    assert spec.mixers == ("kda", "kda", "kda", "mla", "kda") and spec.kda_layers == 4
    assert spec.runs == (("moe", "kda", 0, 2), ("moe1", "mla", 2, 1), ("moe2", "kda", 3, 1))
    assert (spec.routed, spec.per_token, spec.shared_width, spec.rotary) == (16, 3, 32, False)
    assert (spec.kda_heads, spec.kda_dim, spec.conv_kernel, spec.kda_chunk) == (4, 8, 4, 8)
    assert seqlm.SeqSpec.from_cfg(MODEL, _cfg(seq_layers=8)).mixers[-1] == "mla"
    assert seqlm.SeqSpec.from_cfg(MODEL, _cfg(kda_chunk=0, seq_length=64)).kda_chunk == 64


@pytest.mark.parametrize("key, value", [
    ("q_lora_rank", 1536), ("num_expert_group", 8), ("topk_group", 4),
    ("moe_router_activation_func", "softmax"), ("moe_renormalize", False),
    ("mla_use_nope", False), ("moe_layer_freq", 2), ("hidden_act", "gelu"),
])
def test_a_kimi_linear_key_the_family_does_not_compute_is_refused_by_name(key, value):
    with pytest.raises(ValueError, match=f"MODEL_FILE has {key}="):
        seqlm.SeqSpec.from_cfg(dict(MODEL, **{key: value}), _cfg())


def test_a_layer_list_that_disagrees_with_the_depth_is_refused():
    linear = dict(MODEL["linear_attn_config"], kda_layers=[1, 2, 3, 5, 6])
    with pytest.raises(ValueError, match="kda_layers and full_attn_layers"):
        seqlm.SeqSpec.from_cfg(dict(MODEL, linear_attn_config=linear), _cfg())
    with pytest.raises(ValueError, match="KDA_CHUNK:5 does not divide"):
        seqlm.SeqSpec.from_cfg(MODEL, _cfg(kda_chunk=5))


# ---- counters, gauges, scopes

def test_the_counters_and_gauges_of_the_new_layers(tmp_path):
    trainer = make_trainer(tmp_path)
    trainer.run()
    gauges = trainer.metrics.snapshot()["gauges"]
    assert (gauges["seq.kda_layers"], gauges["seq.mla_layers"], gauges["kda.chunk"]) == (4, 1, 8)
    # off the TPU (and at heads of 8) the chunk walk is the scan: no layer lowers to the kernels
    assert (gauges["kda.recur_fused"], gauges["kda.rows_per_block"]) == (0.0, 0)
    assert (gauges["attn.kernel"], gauges["attn.rows_per_tile"]) == (0.0, 0)  # the tile walk, off the TPU
    assert trainer.metrics.counter_get("kda.token_layers") == 2 * 64 * 4  # epochs x tokens x layers
    assert trainer.metrics.counter_get("seq.tokens") == 2 * 64


def test_the_scope_table_of_a_hybrid_step_covers_every_named_scope(trainer):
    table = trainer.scope_table()
    assert set(table.values()) == {s for s in seqlm.SCOPES
                                   if not s.startswith(("seq/gqa/", "seq/sconv/"))}
    assert seqlm.scope_of("jit(step)/transpose(jvp(seq/kda/recur))/while/body/dot") == "seq/kda/recur"


def test_bfloat16_compute_stays_near_the_reference(tmp_path):
    trainer = make_trainer(tmp_path, PRECISION="bfloat16")
    p0 = jax.tree.map(np.asarray, trainer.params)
    trainer.run()
    share = ref.Share(trainer.spec.first, trainer.spec.held)
    want, _ = ref.loss(p0, trainer.datum.tokens[:2], SHAPE, share, block=16)
    assert abs(trainer.loss_history[0] - float(want)) < 1e-3 * float(want)
    assert all(a.dtype == jnp.float32 for a in jax.tree.leaves(trainer.params))  # the masters
