"""ELL-bucketed gather-only aggregation (ops/ell.py, the OPTIM_KERNEL path)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tests.conftest import tiny_graph
from neutronstarlite_tpu.ops.aggregate import gather_dst_from_src
from neutronstarlite_tpu.ops.device_graph import DeviceGraph
from neutronstarlite_tpu.ops.ell import (
    MAX_LEVELS,
    EllPair,
    ell_gather_dst_from_src,
    ell_gather_src_from_dst,
    ell_tables_aggregate,
    level_slots,
    level_widths,
)


def test_ell_forward_matches_dense(rng):
    g, dense = tiny_graph(rng, v_num=83, e_num=700)
    pair = EllPair.from_host(g)
    x = rng.standard_normal((g.v_num, 9)).astype(np.float32)
    out = np.asarray(ell_gather_dst_from_src(pair, jnp.asarray(x)))
    np.testing.assert_allclose(out, dense @ x.astype(np.float64), rtol=1e-4, atol=1e-4)
    # CSR direction: out[u] = sum over out-edges of w * y[v] == dense.T @ y
    y = rng.standard_normal((g.v_num, 9)).astype(np.float32)
    out2 = np.asarray(ell_gather_src_from_dst(pair, jnp.asarray(y)))
    np.testing.assert_allclose(out2, dense.T @ y.astype(np.float64), rtol=1e-4, atol=1e-4)


def test_ell_small_slot_chunk_matches(rng):
    """Row chunking must not change results (exercises the scan path)."""
    g, dense = tiny_graph(rng, v_num=60, e_num=600)
    pair = EllPair.from_host(g, slot_chunk=64)
    x = rng.standard_normal((g.v_num, 5)).astype(np.float32)
    out = np.asarray(ell_gather_dst_from_src(pair, jnp.asarray(x)))
    np.testing.assert_allclose(out, dense @ x.astype(np.float64), rtol=1e-4, atol=1e-4)


def test_ell_grads_match_scatter_path(rng):
    """The ELL custom_vjp must produce the same gradients as the chunked
    sorted-scatter path (the two backends are interchangeable)."""
    g, _ = tiny_graph(rng, v_num=47, e_num=400)
    graph = DeviceGraph.from_host(g)
    pair = EllPair.from_host(g)
    x = jnp.asarray(rng.standard_normal((g.v_num, 6)).astype(np.float32))
    t = jnp.asarray(rng.standard_normal((g.v_num, 6)).astype(np.float32))

    def loss_scatter(x):
        return jnp.sum(gather_dst_from_src(graph, x) * t)

    def loss_ell(x):
        return jnp.sum(gather_dst_from_src(pair, x) * t)

    np.testing.assert_allclose(
        np.asarray(jax.grad(loss_ell)(x)),
        np.asarray(jax.grad(loss_scatter)(x)),
        rtol=1e-4,
        atol=1e-4,
    )


def test_ell_isolated_and_hub_vertices(rng):
    """Degree-0 vertices produce zero rows; a hub vertex lands in a big
    bucket and still aggregates exactly."""
    v = 40
    hub = 7
    src = np.concatenate([np.arange(v), rng.integers(0, v, 200)]).astype(np.uint32)
    dst = np.concatenate([np.full(v, hub), rng.integers(0, v, 200)]).astype(np.uint32)
    from neutronstarlite_tpu.graph.storage import build_graph

    g = build_graph(src, dst, v + 3, weight="ones")  # 3 isolated vertices
    pair = EllPair.from_host(g)
    x = rng.standard_normal((v + 3, 4)).astype(np.float32)
    out = np.asarray(ell_gather_dst_from_src(pair, jnp.asarray(x)))
    dense = np.zeros((v + 3, v + 3))
    np.add.at(dense, (dst.astype(np.int64), src.astype(np.int64)), 1.0)
    np.testing.assert_allclose(out, dense @ x.astype(np.float64), rtol=1e-4, atol=1e-4)
    assert np.all(out[v:] == 0)


def test_gcn_converges_with_optim_kernel():
    """End-to-end GCN with OPTIM_KERNEL:1 (ELL backend)."""
    from tests.test_models import _planted_cfg, _planted_data
    from neutronstarlite_tpu.models.gcn import GCNTrainer

    cfg = _planted_cfg()
    cfg.optim_kernel = True
    src, dst, datum = _planted_data(seed=21)
    trainer = GCNTrainer.from_arrays(cfg, src, dst, datum)
    from neutronstarlite_tpu.ops.ell import EllPair as EP

    assert isinstance(trainer.compute_graph, EP)
    result = trainer.run()
    assert result["acc"]["test"] > 0.85
    assert result["loss"] < 0.5


def test_k_chunked_hub_level_matches_plain(rng, monkeypatch):
    """A hub level whose K alone exceeds the byte budget takes the K-chunked
    scan; the f32 running sum must match the single-pass reduction."""
    V, f, Nk, K = 64, 4, 2, 1 << 18  # K slots > 1 MiB budget at f=4
    nbr = rng.integers(0, V, size=(Nk, K)).astype(np.int32)
    wgt = rng.standard_normal((Nk, K)).astype(np.float32) * 0.01
    x = rng.standard_normal((V, f)).astype(np.float32)
    want = (x[nbr].astype(np.float64) * wgt[:, :, None]).sum(axis=1)

    monkeypatch.setenv("NTS_ELL_CHUNK_MIB", "1")
    out = ell_tables_aggregate(jnp.asarray(x), [jnp.asarray(nbr)],
                               [jnp.asarray(wgt)], slot_chunk=1 << 21)
    np.testing.assert_allclose(np.asarray(out, np.float64), want,
                               rtol=1e-4, atol=1e-4)


# ---- the choice of level widths (ops/ell.level_widths) ---------------------

def _pow2_ladder(degrees_per_device):
    """The widths the builders used before level_widths: 4, 8, 16, ... up
    to the next power of two at or above the largest degree."""
    top = max(int(np.max(d)) for d in degrees_per_device if len(d))
    widths = [4]
    while widths[-1] < top:
        widths.append(2 * widths[-1])
    return widths


def _power_law_degrees(n, seed):
    r = np.random.default_rng(seed)
    return (3 + 40000 * r.random(n) ** 9).astype(np.int64)


_DEGREE_CASES = {
    "uniform": [np.full(500, 37)],
    "hub_among_leaves": [np.concatenate([np.ones(999, np.int64), [100_000]])],
    "all_zero": [np.zeros(64, np.int64)],
    "single_vertex": [np.array([13])],
    "single_zero_vertex": [np.array([0])],
    "just_above_pow2": [np.concatenate([
        np.full(300, 2 ** k + 1) for k in range(2, 13)
    ])],
    "power_law": [_power_law_degrees(20000, 5)],
    "power_law_four_devices": [_power_law_degrees(5000, s) for s in range(4)],
    "more_degrees_than_levels": [np.arange(1, 2000)],
    "empty": [np.zeros(0, np.int64)],
}


@pytest.mark.parametrize("case", sorted(_DEGREE_CASES))
def test_level_widths_cover_every_degree_within_the_cap(case):
    degs = _DEGREE_CASES[case]
    widths = level_widths(degs)
    top = max((int(d.max()) for d in degs if d.size), default=0)
    if top == 0:
        assert widths.size == 0  # zero degrees keep their K=0 bucket
        return
    assert widths[-1] >= top
    assert 1 <= len(widths) <= MAX_LEVELS
    assert np.all(np.diff(widths) > 0)
    assert np.all((widths == 4) | (widths % 8 == 0)), widths
    # every level holds a row on some device
    stacked = np.concatenate(degs)
    edges = np.concatenate([[0], widths])
    assert np.all(np.histogram(stacked[stacked > 0], bins=edges + 0.5)[0] > 0)


@pytest.mark.parametrize(
    "case", [c for c in sorted(_DEGREE_CASES) if c not in ("all_zero", "empty", "single_zero_vertex")]
)
def test_level_widths_never_cost_more_than_the_pow2_ladder(case):
    degs = _DEGREE_CASES[case]
    ours = level_slots(level_widths(degs), degs)
    ladder = level_slots(_pow2_ladder(degs), degs)
    real = sum(int(d.sum()) for d in degs) / len(degs)
    assert real <= ours <= ladder, (ours, ladder)


def test_level_widths_cut_the_adversarial_ladder_case_by_a_third():
    """Degrees one above a power of two: the ladder pads each to nearly
    twice its size, the chosen widths to the next multiple of 8."""
    degs = _DEGREE_CASES["just_above_pow2"]
    assert level_slots(level_widths(degs), degs) < 0.67 * level_slots(
        _pow2_ladder(degs), degs
    )


def _brute_force_slots(degs, cap):
    from itertools import combinations

    from neutronstarlite_tpu.ops.ell import _aligned_width

    cand = np.unique(_aligned_width(np.concatenate(degs)))
    return min(
        level_slots(list(sub) + [cand[-1]], degs)
        for n in range(min(cap, cand.size))
        for sub in combinations(cand[:-1], n)
    )


@pytest.mark.parametrize("seed", range(12))
def test_level_widths_equal_the_brute_force_optimum(seed):
    """Every subset of the candidate widths (up to 8 distinct degrees, 1
    to 3 devices, caps 1 to 5): the dynamic program finds the cheapest."""
    r = np.random.default_rng(seed)
    values = r.choice(np.arange(1, 300), size=r.integers(1, 9), replace=False)
    degs = [r.choice(values, size=r.integers(1, 40)) for _ in range(r.integers(1, 4))]
    cap = int(r.integers(1, 6))
    widths = level_widths(degs, cap)
    assert len(widths) <= cap
    assert level_slots(widths, degs) == _brute_force_slots(degs, cap)


def test_level_widths_price_a_level_at_its_fullest_device():
    """Stacked tables pad every level to its fullest device, so a level
    costs ``K x max_p rows_p``: ten hubs of degree 1000 on one device only
    do not buy themselves a level, which the other three devices would
    each pad to ten rows; they ride in the level of the others' ten rows
    of degree 1200. Alone, that device keeps the tighter width."""
    leaves = np.full(400, 20)
    with_hubs = np.concatenate([leaves, np.full(10, 1000)])
    others = np.concatenate([leaves, np.full(10, 1200)])
    stacked = [with_hubs, others, others, others]
    assert level_widths([with_hubs]).tolist() == [24, 1000]
    assert level_widths(stacked).tolist() == [24, 1200]
    # the cost is per device, at the fullest device's rows
    assert level_slots([24, 1200], stacked) == 24 * 400 + 1200 * 10
    assert level_slots([24, 1000, 1200], stacked) == 24 * 400 + 1000 * 10 + 1200 * 10
    # and rows on different devices share the padding: one device's 400
    # rows at 16 and another's at 24 are cheaper in one level than in two
    split = [np.full(400, 16), np.full(400, 24)]
    assert level_widths(split).tolist() == [24]
    assert level_widths([np.concatenate(split)]).tolist() == [16, 24]


def test_level_widths_price_whole_row_chunks():
    """A level scanned in row chunks is priced with its last chunk's
    padding, as ell_tables_aggregate walks it."""
    from neutronstarlite_tpu.ops.ell import _PRICED_CHUNK_SLOTS

    K = 128
    chunk = _PRICED_CHUNK_SLOTS // K
    assert level_slots([K], [np.full(chunk, K)]) == K * chunk
    assert level_slots([K], [np.full(chunk + 1, K)]) == 2 * K * chunk


def test_hub_levels_keep_power_of_two_widths():
    """Above 8192 a level can take the K-chunked path, which on the chip
    summed bf16 reads wrongly where K was no power of two: those widths
    stay on the ladder, the narrower ones follow the histogram."""
    degs = [np.concatenate([
        np.full(500, 100), np.full(40, 3001), np.full(9, 9000),
        np.full(6, 161_553), np.full(2, 227_761), [1_858_041],
    ])]
    widths = level_widths(degs).tolist()
    assert widths == [104, 3008, 16384, 262144, 2097152]
    power_law = level_widths(_DEGREE_CASES["power_law"])
    wide = power_law[power_law > 8192]
    assert wide.size and np.all(wide & (wide - 1) == 0)
    assert np.any(power_law & (power_law - 1))  # the narrow ones are chosen


def test_ell_tables_hold_fewer_slots_than_the_ladder(rng):
    """The built tables, not only the priced count: a power-law graph's
    EllPair holds every edge, at most the cap's levels, and under 1.25
    slots an edge where the ladder held 1.4."""
    from neutronstarlite_tpu.graph.storage import build_graph
    from neutronstarlite_tpu.graph.synthetic import synthetic_power_law_graph

    src, dst = synthetic_power_law_graph(3000, 600_000, seed=3)
    g = build_graph(src, dst, 3000, weight="gcn_norm")
    pair = EllPair.from_host(g)
    stats = pair.padding_stats(g.e_num)
    assert stats["levels"] <= MAX_LEVELS
    for side, table in (("fwd", pair.fwd), ("bwd", pair.bwd)):
        assert sum(int((np.asarray(w) != 0).sum()) for w in table.wgt) == g.e_num
        assert 1.0 <= stats[f"{side}_waste_ratio"] < 1.25, stats
    deg = np.diff(g.column_offset)
    assert stats["fwd_slots"] < 0.85 * level_slots(_pow2_ladder([deg]), [deg])


# ---- parity over widths that are no powers of two --------------------------


def _power_law_rig(v_num=800, e_num=30_000, seed=3):
    from neutronstarlite_tpu.graph.storage import build_graph
    from neutronstarlite_tpu.graph.synthetic import synthetic_power_law_graph

    src, dst = synthetic_power_law_graph(v_num, e_num, seed=seed)
    return build_graph(src, dst, v_num, weight="gcn_norm")


def _paths_taken(buckets, f, budget_bytes):
    """Which of ell_tables_aggregate's three paths each level takes at
    feature width ``f`` (f32 slab) under ``budget_bytes``."""
    from neutronstarlite_tpu.ops.ell import _chunk_rows

    slot_budget = budget_bytes // (f * 4)
    kinds = set()
    for nbr in buckets.nbr:
        Nk, K = nbr.shape
        if K == 0:
            continue
        if K > slot_budget:
            kinds.add("k_chunked")
        elif Nk <= int(_chunk_rows(min(buckets.slot_chunk, slot_budget), K)):
            kinds.add("direct")
        else:
            kinds.add("row_chunked")
    return kinds


@pytest.mark.parametrize("what", ["forward", "vjp"])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-4), (jnp.bfloat16, 3e-2)])
def test_ell_parity_over_non_pow2_widths_all_three_paths(monkeypatch, rng, dtype, tol, what):
    """EllPair against the sorted-scatter reference on a power-law graph
    whose chosen widths are no powers of two, with the byte budget and the
    slot chunk small enough that the direct, the row-chunked and the
    K-chunked hub path all run."""
    g = _power_law_rig()
    f = 512
    monkeypatch.setenv("NTS_ELL_CHUNK_MIB", "1")  # 512 slots a chunk at f=512
    pair = EllPair.from_host(g, slot_chunk=256)
    widths = [n.shape[1] for n in pair.fwd.nbr if n.shape[1]]
    assert any(k & (k - 1) for k in widths), widths  # not the ladder
    for table in (pair.fwd, pair.bwd):
        assert _paths_taken(table, f, 1 << 20) == {"direct", "row_chunked", "k_chunked"}
    graph = DeviceGraph.from_host(g)
    x = jnp.asarray(rng.standard_normal((g.v_num, f)).astype(np.float32))
    t = jnp.asarray(rng.standard_normal((g.v_num, f)).astype(np.float32))
    if what == "forward":
        got = gather_dst_from_src(pair, x.astype(dtype))
        want = gather_dst_from_src(graph, x)
    else:
        got = jax.grad(lambda x: jnp.sum(
            gather_dst_from_src(pair, x.astype(dtype)).astype(jnp.float32) * t
        ))(x)
        want = jax.grad(lambda x: jnp.sum(gather_dst_from_src(graph, x) * t))(x)
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert np.abs(got - want).max() <= tol * np.abs(want).max()


def test_row_chunks_are_multiples_of_eight_for_any_width(monkeypatch):
    """rows = budget // K rounded down to a multiple of 8 once it reaches
    8 (the compiler's sublane tiling), for widths that divide nothing."""
    from neutronstarlite_tpu.ops.ell import _chunk_rows

    for K, slots, want in ((136, 65536, 480), (24, 1000, 40), (4, 65536, 16384),
                           (200, 1000, 5), (5000, 1000, 1)):
        assert int(_chunk_rows(slots, K)) == want
    # and the scan over such chunks drops nothing: 1000 rows of width 24
    # at 40 rows a chunk
    r = np.random.default_rng(2)
    nbr = r.integers(0, 50, size=(1000, 24)).astype(np.int32)
    wgt = r.standard_normal((1000, 24)).astype(np.float32)
    x = r.standard_normal((50, 8)).astype(np.float32)
    out = ell_tables_aggregate(jnp.asarray(x), [jnp.asarray(nbr)], [jnp.asarray(wgt)], slot_chunk=1000)
    want = (x[nbr].astype(np.float64) * wgt[:, :, None]).sum(axis=1)
    np.testing.assert_allclose(np.asarray(out, np.float64), want, rtol=1e-4, atol=1e-4)
