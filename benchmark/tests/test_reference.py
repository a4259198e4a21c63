"""The plain reference against dense linear algebra on a small graph."""

import numpy as np
import pytest

from harness import data, spec
from reference import gcn


@pytest.fixture(scope="module")
def small():
    v = 200
    src, dst = data.power_law_edges(v, 3000, seed=3)
    out_deg, in_deg = gcn.degrees(src, dst, v)
    order = np.argsort(dst, kind="stable")
    by_dst = gcn.Edges(src[order], dst[order],
                       gcn.edge_weights(src[order], dst[order], out_deg, in_deg))
    order = np.argsort(src, kind="stable")
    by_src = gcn.Edges(dst[order], src[order],
                       gcn.edge_weights(src[order], dst[order], out_deg, in_deg))
    a = np.zeros((v, v), np.float64)
    # repeated edges add, as they do in the program
    np.add.at(a, (dst, src), 1.0 / np.sqrt(np.bincount(src, minlength=v)[src].astype(np.float64)
                                           * np.bincount(dst, minlength=v)[dst]))
    rng = np.random.default_rng(0)
    x = rng.standard_normal((v, 12)).astype(np.float32)
    return v, by_dst, by_src, a, x, rng


def test_aggregate_is_a_times_x_in_any_chunking_and_its_transpose(small):
    v, by_dst, by_src, a, x, _ = small
    for chunk in (64, 1000, 1 << 18):
        got = np.asarray(gcn.aggregate(by_dst, x, v, chunk=chunk))
        np.testing.assert_allclose(got, a @ x.astype(np.float64), rtol=1e-5, atol=1e-5)
        got = np.asarray(gcn.aggregate(by_src, x, v, chunk=chunk))
        np.testing.assert_allclose(got, a.T @ x.astype(np.float64), rtol=1e-5, atol=1e-5)


def two_layer_params(rng):
    return [{"W": rng.standard_normal((12, 8)).astype(np.float32),
             "bn": {"gamma": rng.standard_normal(12).astype(np.float32),
                    "beta": rng.standard_normal(12).astype(np.float32)}},
            {"W": rng.standard_normal((8, 5)).astype(np.float32)}]


def dense_two_layers(a, params, x):
    """The same model with a dense adjacency, in jax.numpy at float64."""
    import jax.numpy as jnp

    bn = params[0]["bn"]
    h = a @ x
    h = (h - h.mean(0)) / jnp.sqrt(h.var(0) + gcn.BN_EPS) * bn["gamma"] + bn["beta"]
    return (a @ jnp.maximum(h @ params[0]["W"], 0.0)) @ params[1]["W"]


def test_two_layers_against_dense(small):
    v, by_dst, _, a, x, rng = small
    params = two_layer_params(rng)
    got = gcn.full_forward(by_dst, params, x)
    want = dense_two_layers(a, params, x.astype(np.float64))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_the_input_aggregate_made_once_serves_both_passes(small):
    v, by_dst, by_src, _, x, rng = small
    first, second = two_layer_params(rng), two_layer_params(rng)
    label = rng.integers(0, 5, v).astype(np.int32)
    mask01 = np.ones(v, np.float32)
    a0 = gcn.aggregate_input(by_dst, x)
    assert np.array_equal(gcn.full_forward(by_dst, first, x, a0), gcn.full_forward(by_dst, first, x))
    with_a0 = gcn.full_loss_and_grads(by_dst, by_src, second, x, label, mask01, a0)
    without = gcn.full_loss_and_grads(by_dst, by_src, second, x, label, mask01)
    assert np.array_equal(with_a0[0], without[0]) and with_a0[1] == without[1]
    for got, want in zip(with_a0[2], without[2]):
        assert np.array_equal(got["W"], want["W"])


def test_loss_and_gradients_against_autodiff_of_the_dense_model(small):
    import jax

    v, by_dst, by_src, a, x, rng = small
    params = two_layer_params(rng)
    label = rng.integers(0, 5, v).astype(np.int32)
    mask01 = (rng.random(v) < 0.6).astype(np.float32)
    logits, loss, grads = gcn.full_loss_and_grads(by_dst, by_src, params, x, label, mask01)

    jax.config.update("jax_enable_x64", True)
    try:
        p64 = jax.tree.map(lambda w: w.astype(np.float64), params)

        def dense_loss(p):
            logp = jax.nn.log_softmax(dense_two_layers(a, p, x.astype(np.float64)), axis=-1)
            return -(logp[np.arange(v), label] * mask01).sum() / mask01.sum()

        want_loss, want = jax.value_and_grad(dense_loss)(p64)
        want = jax.tree.map(np.asarray, want)
    finally:
        jax.config.update("jax_enable_x64", False)
    assert loss == pytest.approx(float(want_loss), rel=1e-4)
    np.testing.assert_allclose(logits, gcn.full_forward(by_dst, params, x), rtol=1e-6)
    for got_leaf, want_leaf in zip(jax.tree.leaves(grads), jax.tree.leaves(want)):
        np.testing.assert_allclose(got_leaf, want_leaf, rtol=2e-3, atol=2e-5)


@pytest.fixture(scope="module")
def blocks():
    rng = np.random.default_rng(1)
    caps = [30, 10, 4]
    x0 = rng.standard_normal((30, 6)).astype(np.float32)
    hops, mats = [], []
    for n_in, n_out in zip(caps[:-1], caps[1:]):
        e = 3 * n_out
        s, d = rng.integers(0, n_in, e), rng.integers(0, n_out, e)
        w = rng.random(e).astype(np.float32)
        m = np.zeros((n_out, n_in))
        np.add.at(m, (d, s), w)
        hops.append((s, d, w))
        mats.append(m)
    params = [{"W": rng.standard_normal((6, 5)).astype(np.float32)},
              {"W": rng.standard_normal((5, 3)).astype(np.float32)}]
    return caps, x0, hops, mats, params, rng


def test_block_forward_against_dense(blocks):
    caps, x0, hops, mats, params, _ = blocks
    got = gcn.block_forward(params, x0, hops, caps)
    h = np.maximum((mats[0] @ x0) @ params[0]["W"], 0.0)
    want = (mats[1] @ h) @ params[1]["W"]
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_block_gradients_by_finite_differences(blocks):
    caps, x0, hops, _, params, rng = blocks
    label = rng.integers(0, 3, caps[-1]).astype(np.int32)
    mask01 = np.asarray([1, 1, 1, 0], np.float32)  # the last seed is padding
    loss, grads = gcn.block_loss_and_grads(params, x0, hops, caps, label, mask01)

    def loss_at(p):
        logits = gcn.block_forward(p, x0, hops, caps).astype(np.float64)
        logp = logits - np.log(np.exp(logits).sum(1, keepdims=True))
        return -(logp[np.arange(4), label] * mask01).sum() / mask01.sum()

    assert loss == pytest.approx(loss_at(params), rel=1e-5)
    for layer, (i, j) in ((0, (2, 3)), (1, (4, 1))):
        step = np.zeros_like(params[layer]["W"])
        step[i, j] = 1e-2
        up = [dict(p) for p in params]
        down = [dict(p) for p in params]
        up[layer]["W"] = params[layer]["W"] + step
        down[layer]["W"] = params[layer]["W"] - step
        assert grads[layer]["W"][i, j] == pytest.approx(
            (loss_at(up) - loss_at(down)) / 2e-2, rel=2e-2, abs=1e-4)


def test_block_weights_are_the_whole_graphs_and_zero_on_padding():
    out_deg, in_deg = np.asarray([4, 0, 9]), np.asarray([1, 16, 0])
    nodes = [np.asarray([0, 2]), np.asarray([1, 0])]
    hops = [(np.asarray([0, 1, 1]), np.asarray([0, 0, 1]), np.asarray([True, True, False]))]
    (_, _, w), = gcn.block_weights(nodes, hops, out_deg, in_deg)
    # 0 -> 1: 1/sqrt(4 * 16); 2 -> 1: 1/sqrt(9 * 16); the third slot is padding
    np.testing.assert_allclose(w, [1 / 8, 1 / 12, 0.0])


def test_generator_is_seeded_and_counts_edges():
    a = data.power_law_edges(100, 1000, seed=1)
    b = data.power_law_edges(100, 1000, seed=1)
    assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])
    assert len(a[0]) == 1000
    s, d = data.power_law_edges(100, 1100, seed=1, symmetric=True)
    assert len(s) == 1100
    pairs = set(zip(s.tolist(), d.tolist()))
    assert all((y, x) in pairs for x, y in pairs)


def test_datum_is_seeded_and_split_as_asked():
    make_datum = spec.named_module("inputs", "vertex_graph").make_datum
    f1, l1, m1 = make_datum(100, 8, 5, [60, 10, 30], seed=4)
    f2, l2, m2 = make_datum(100, 8, 5, [60, 10, 30], seed=4)
    assert np.array_equal(f1, f2) and np.array_equal(l1, l2) and np.array_equal(m1, m2)
    assert np.bincount(m1).tolist() == [60, 10, 30]
    assert f1.dtype == np.float32 and l1.max() < 5
