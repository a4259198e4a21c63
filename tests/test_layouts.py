"""The two places a cfg becomes aggregation tables: ``ops/aggregate.
build_tables`` (one chip) and ``parallel/layouts.build_exchange`` (the
partitioned plane). For every combination a builder accepts: the returned
type, what ``describe()`` calls it, and both directions against the
DeviceGraph scatter reference."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tests.conftest import tiny_graph
from neutronstarlite_tpu.models.base import get_algorithm
from neutronstarlite_tpu.ops.aggregate import (
    build_tables,
    gather_dst_from_src,
    gather_src_from_dst,
)
from neutronstarlite_tpu.ops.device_graph import DeviceGraph
from neutronstarlite_tpu.parallel.layouts import build_exchange
from neutronstarlite_tpu.utils.config import InputInfo


def _cfg(algorithm="GCNCPU", **over):
    cfg = InputInfo()
    cfg.algorithm = algorithm
    cfg.vertices = 23
    cfg.layer_string = "5-4-3"
    for key, value in over.items():
        setattr(cfg, key, value)
    return cfg


# ---- one chip ---------------------------------------------------------------

@pytest.mark.parametrize(
    "pallas,tile,expected,named",
    [
        (False, 0, "EllPair", "ELL gather-only"),
        (False, 64, "BlockedEllPair", "blocked ELL"),
        (True, 0, "BspEllPair", "block-sparse Pallas"),
        (True, 64, "BspEllPair", "vt=64"),
    ],
)
def test_build_tables_type_and_both_directions(rng, pallas, tile, expected, named):
    g, dense = tiny_graph(rng)
    cfg = _cfg(optim_kernel=True, pallas_kernel=pallas, kernel_tile=tile)
    pair, stats = build_tables(cfg, g)
    assert type(pair).__name__ == expected
    assert named in pair.describe()
    # only the level tables have levels to report (gauges agg.*)
    assert (stats is not None) == (expected == "EllPair")
    if stats is not None:
        assert stats["real_edges"] == g.e_num
        assert stats["fwd_slots"] >= g.e_num and stats["levels"] >= 1

    ref = DeviceGraph.from_host(g)
    x = jnp.asarray(rng.standard_normal((g.v_num, 5)).astype(np.float32))
    for op in (gather_dst_from_src, gather_src_from_dst):
        np.testing.assert_allclose(
            np.asarray(op(pair, x)), np.asarray(op(ref, x)),
            rtol=2e-2 if pallas else 1e-5, atol=2e-2 if pallas else 1e-5,
        )
    # the pair's backward is its own other direction
    cot = jnp.asarray(rng.standard_normal((g.v_num, 5)).astype(np.float32))
    grad = jax.grad(lambda v: jnp.sum(gather_dst_from_src(pair, v) * cot))(x)
    np.testing.assert_allclose(
        np.asarray(grad), dense.T @ np.asarray(cot, np.float64),
        rtol=2e-2 if pallas else 1e-4, atol=2e-2 if pallas else 1e-4,
    )


@pytest.mark.parametrize("tile", [0, 64])
def test_without_optim_kernel_the_builder_is_not_reached(tile):
    """OPTIM_KERNEL:0 keeps the DeviceGraph scatter path, whatever
    KERNEL_TILE says: the funnel uploads the DeviceGraph and build_model
    never asks for tables."""
    t = get_algorithm("GCNCPU")(_cfg(optim_kernel=False, kernel_tile=tile))
    t._check_kernel()
    assert not t._wants_ell() and t._build_device_graph()


@pytest.mark.parametrize(
    "over,message",
    [
        (dict(pallas_kernel=True), "PALLAS:1 requires OPTIM_KERNEL"),
        (dict(pallas_kernel=True, kernel_tile=64), "PALLAS:1 requires OPTIM_KERNEL"),
        (dict(kernel="fused_edge", optim_kernel=True), "different kernel stacks"),
        (
            dict(kernel="fused_edge", optim_kernel=True, pallas_kernel=True),
            "different kernel stacks",
        ),
    ],
)
def test_refused_at_the_funnel_before_any_builder(over, message):
    algorithm = "GATCPU" if over.get("kernel") else "GCNCPU"
    t = get_algorithm(algorithm)(_cfg(algorithm, **over))
    with pytest.raises(ValueError, match=message):
        t._check_kernel()


# ---- the partitioned plane --------------------------------------------------

P = 4

_EXCHANGES = [
    # comm_layer, dist_path, pallas, tile -> kind, blocks type, named
    ("ring", "", False, 0, "ring", "RingBlocks", "ppermute ring"),
    ("ell", "", False, 0, "ell", "DistEllPair", "ELL tables"),
    ("mirror", "", False, 0, "mirror", "SplitMirrorTables", "split mirror"),
    ("ring", "all_gather", False, 0, "ell", "DistEllPair", "ELL tables"),
    ("ell", "all_gather", False, 0, "ell", "DistEllPair", "ELL tables"),
    ("mirror", "all_gather", False, 0, "ell", "DistEllPair", "ELL tables"),
    ("ring", "ring_blocked_sim", False, 0, "ring_blocked", "RingBlockedPair", "double-buffered ring"),
    ("ell", "ring_blocked_sim", False, 0, "ring_blocked", "RingBlockedPair", "double-buffered ring"),
    ("mirror", "ring_blocked_sim", False, 0, "ring_blocked", "RingBlockedPair", "double-buffered ring"),
    ("ell", "", False, 64, "ell", "DistBlockedEllPair", "dist blocked"),
    ("ell", "", True, 0, "ell", "DistBspPair", "dist bsp"),
    ("auto", "", False, 0, None, None, None),  # resolved from the wire rows
]


@pytest.mark.parametrize(
    "comm_layer,dist_path,pallas,tile,kind,expected,named", _EXCHANGES
)
def test_build_exchange_type_and_both_directions(
    rng, comm_layer, dist_path, pallas, tile, kind, expected, named
):
    if len(jax.devices()) < P:
        pytest.skip("needs the 8-virtual-device rig")
    g, dense = tiny_graph(rng)
    cfg = _cfg(
        "GCNDIST", comm_layer=comm_layer, dist_path=dist_path, partitions=P,
        optim_kernel=pallas, pallas_kernel=pallas, kernel_tile=tile,
    )
    plan = build_exchange(cfg, g)
    if kind is None:
        from neutronstarlite_tpu.parallel.mirror import SplitMirror

        mb, vp = SplitMirror.estimate_mb_remote(g, P)
        kind, expected, named = (
            ("mirror", "SplitMirrorTables", "split mirror") if mb <= vp
            else ("ring", "RingBlocks", "ppermute ring")
        )
    assert plan.kind == kind and plan.partitions == P
    assert type(plan.blocks).__name__ == expected
    assert named in plan.blocks.describe()
    assert (plan.mesh is None) == (dist_path == "ring_blocked_sim")
    assert (plan.table_stats is not None) == (expected == "DistEllPair")
    assert plan.dist.partitions == P and plan.dist.v_num == g.v_num

    # both directions against the single-chip aggregate
    ref = DeviceGraph.from_host(g)
    x = rng.standard_normal((g.v_num, 5)).astype(np.float32)
    cot = rng.standard_normal((g.v_num, 5)).astype(np.float32)
    want = np.asarray(gather_dst_from_src(ref, jnp.asarray(x)))
    want_back = np.asarray(gather_src_from_dst(ref, jnp.asarray(cot)))
    xp = jnp.asarray(plan.dist.pad_vertex_array(x))
    cotp = jnp.asarray(plan.dist.pad_vertex_array(cot))

    @jax.jit
    def both(blocks, v):
        out, vjp = jax.vjp(
            lambda u: blocks.exchange(plan.mesh, u, wire_dtype=plan.wire_dtype),
            v,
        )
        return out, vjp(cotp)[0]

    out, back = both(plan.blocks, xp)
    tol = 2e-2 if pallas else 1e-4
    np.testing.assert_allclose(
        plan.dist.unpad_vertex_array(np.asarray(out)), want, rtol=tol, atol=tol
    )
    np.testing.assert_allclose(
        plan.dist.unpad_vertex_array(np.asarray(back)), want_back,
        rtol=tol, atol=tol,
    )


def test_build_exchange_on_the_host_for_a_mesh_handed_in(rng):
    """tools/aot_check's use: the same layout over a mesh it is given,
    nothing placed on that mesh's devices."""
    from jax.sharding import Mesh

    from neutronstarlite_tpu.parallel.mesh import PARTITION_AXIS

    if len(jax.devices()) < P:
        pytest.skip("needs the 8-virtual-device rig")
    g, _ = tiny_graph(rng)
    mesh = Mesh(np.array(jax.devices()[:P]), (PARTITION_AXIS,))
    cfg = _cfg("GCNDIST", comm_layer="ell", partitions=P)
    placed = build_exchange(cfg, g)
    host = build_exchange(cfg, g, mesh=mesh, shard=False)
    assert host.mesh is mesh
    assert jax.tree.structure(host.blocks) == jax.tree.structure(placed.blocks)
    for a, b in zip(jax.tree.leaves(host.blocks), jax.tree.leaves(placed.blocks)):
        assert isinstance(a, np.ndarray)
        np.testing.assert_array_equal(a, np.asarray(b))
    cfg.mesh = "2,2"
    with pytest.raises(ValueError, match="placed by the partitioner"):
        build_exchange(cfg, g, mesh=mesh)
