"""Ring-pipelined distributed aggregation (parallel/dist_ring_blocked.py,
ISSUE 4): the dist-sim parity suite plus the cfg smoke.

Contracts pinned here:
- ring_blocked and the all_gather blocked path compute the SAME
  aggregation (allclose in f32) on 2/4/8 simulated partitions;
- the real shard_map ring is BITWISE equal to its collective-free twin
  (both run the identical step order with one f32 accumulator);
- the static skip schedule drops empty partition pairs at trace time and
  a skipped suffix drops its rotation hops;
- WIRE_DTYPE:bf16 stays within a bf16-mantissa tolerance of the f32 wire
  while accumulating in f32;
- the backward is the reverse ring over transposed tables (jax.grad on a
  2-layer GCN matches the all_gather trainer's whole loss curve);
- the structural memory claim: the ring body's jaxpr holds NO [P*vp, f]
  intermediate (the all_gather body does) — O(2*vp) exchange residency;
- the smoke cfg's obs stream carries ring_step records whose bytes sum
  to the tools/wire_accounting prediction.
"""

from __future__ import annotations

import glob
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tests.conftest import tiny_graph
from neutronstarlite_tpu.parallel.dist_graph import DistGraph
from neutronstarlite_tpu.parallel.dist_ring_blocked import (
    RingBlockedPair,
    dist_ring_blocked_gather_simulated,
    ring_blocked_apply_simulated,
    ring_wire_plan,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

multidevice = pytest.mark.skipif(
    os.environ.get("NTS_MULTIDEVICE", "1") == "0",
    reason="XLA:CPU collectives starve on a single-core host",
)


def _rig(rng, P, v_num=97, e_num=800):
    g, dense = tiny_graph(rng, v_num=v_num, e_num=e_num)
    dg = DistGraph.build(g, P, edge_chunk=64)
    return g, dense, dg


# ---- forward/backward parity vs the all_gather blocked path ----------------


@pytest.mark.parametrize("P", [2, 4, 8])
def test_ring_matches_all_gather_blocked_sim(rng, P):
    """Same DistGraph, same vt: the pipelined ring and the monolithic
    all_gather blocked path agree (both accumulate f32)."""
    from neutronstarlite_tpu.parallel.dist_blocked import (
        DistBlockedEll,
        dist_blocked_gather_simulated,
    )

    g, dense, dg = _rig(rng, P, v_num=64, e_num=420)
    pair = RingBlockedPair.build(dg, vt=16)
    dbl = DistBlockedEll.build(dg, vt=16)
    x = rng.standard_normal((g.v_num, 11)).astype(np.float32)
    xp = jnp.asarray(dg.pad_vertex_array(x))
    ring = np.asarray(ring_blocked_apply_simulated(pair.fwd, xp))
    ag = np.asarray(dist_blocked_gather_simulated(dbl, xp))
    np.testing.assert_allclose(ring, ag, rtol=1e-5, atol=1e-5)
    # and both match the dense golden
    out = dg.unpad_vertex_array(ring)
    np.testing.assert_allclose(
        out, dense @ x.astype(np.float64), rtol=1e-4, atol=1e-4
    )


@pytest.mark.parametrize("P", [2])
def test_ring_backward_matches_dense_transpose(rng, P):
    """grad through the sim pair runs the reverse ring over the
    transposed step tables: grad_x = A^T @ cotangent (P=4's backward is
    additionally covered through the real collective by the smoke run's
    training epochs and the trainer-parity test)."""
    g, dense, dg = _rig(rng, P)
    pair = RingBlockedPair.build(dg, vt=16)
    x = rng.standard_normal((g.v_num, 7)).astype(np.float32)
    xp = jnp.asarray(dg.pad_vertex_array(x))
    t = jnp.asarray(rng.standard_normal(xp.shape).astype(np.float32))
    grad = np.asarray(
        jax.grad(
            lambda v: jnp.sum(dist_ring_blocked_gather_simulated(pair, v) * t)
        )(xp)
    )
    tg = dg.unpad_vertex_array(np.asarray(t))
    expected = dg.pad_vertex_array(
        (dense.T @ tg.astype(np.float64)).astype(np.float32)
    )
    np.testing.assert_allclose(grad, expected, rtol=1e-4, atol=1e-4)


@multidevice
def test_ring_real_collective_bitwise_matches_sim(rng):
    """The shard_map ring (real ppermute collectives on the virtual mesh)
    is BITWISE equal to the collective-free twin: identical step order,
    identical f32 accumulator — the ISSUE 4 'bitwise where both
    accumulate f32' clause."""
    from neutronstarlite_tpu.parallel.dist_ring_blocked import (
        dist_ring_blocked_gather_dst_from_src,
    )
    from neutronstarlite_tpu.parallel.dist_ops import vertex_sharded
    from neutronstarlite_tpu.parallel.mesh import make_mesh

    P = 4
    g, dense, dg = _rig(rng, P, v_num=64, e_num=420)
    pair = RingBlockedPair.build(dg, vt=16)
    mesh = make_mesh(P)
    pair_s = pair.shard(mesh)
    x = rng.standard_normal((g.v_num, 5)).astype(np.float32)
    xp = vertex_sharded(mesh, dg.pad_vertex_array(x))
    real = np.asarray(dist_ring_blocked_gather_dst_from_src(mesh, pair_s, xp))
    sim = np.asarray(
        ring_blocked_apply_simulated(
            pair.fwd, jnp.asarray(dg.pad_vertex_array(x))
        )
    )
    assert np.array_equal(real, sim)
    # (the REVERSE ring through the real collective is exercised by the
    # smoke run's training epochs — jax.grad through the same shard_map;
    # its numeric contract is pinned by the sim grad test above, whose
    # twin is bitwise-equal to the collective path by THIS test)


# ---- static skip schedule --------------------------------------------------


def _block_banded_graph(V, P, hops=(0, 1)):
    """Graph whose edges only connect partition p's dsts to srcs in
    partitions p+h (h in hops) — every other (p, q) pair is EMPTY."""
    from neutronstarlite_tpu.graph.storage import build_graph

    per = V // P
    src, dst = [], []
    for p in range(P):
        for h in hops:
            base_s = ((p + h) % P) * per
            base_d = p * per
            for i in range(per):
                src.append(base_s + i)
                dst.append(base_d + i)
    return build_graph(
        np.asarray(src, np.uint32), np.asarray(dst, np.uint32), V,
        weight="gcn_norm",
    ), np.asarray(src), np.asarray(dst)


def test_ring_skip_schedule_drops_empty_pairs(rng):
    """A block-banded graph (edges only at ring offsets 0 and 1) must
    skip steps 2..P-1 at trace time AND trim the rotation to one hop —
    while still aggregating correctly."""
    from neutronstarlite_tpu.graph.storage import gcn_norm_weights

    V, P = 64, 4
    g, src, dst = _block_banded_graph(V, P, hops=(0, 1))
    dg = DistGraph.build(g, P)
    pair = RingBlockedPair.build(dg, vt=8)
    assert pair.fwd.work_steps() == [0, 1]
    assert pair.fwd.skipped_steps() == [2, 3]
    assert pair.fwd.n_transfers() == 1  # skipped SUFFIX drops its hops
    # reverse direction: src partition p feeds dsts in p and p-1; the
    # bwd ring (direction -1) holds cotangent shard q = p - s at step s,
    # so work is at q in {p, p-1} -> steps [0, 1], suffix trimmed too
    assert pair.bwd.work_steps() == [0, 1]
    assert pair.bwd.n_transfers() == 1

    w = gcn_norm_weights(
        src.astype(np.int64), dst.astype(np.int64),
        g.out_degree, g.in_degree,
    )
    dense = np.zeros((V, V))
    np.add.at(dense, (dst.astype(np.int64), src.astype(np.int64)), w)
    x = rng.standard_normal((V, 5)).astype(np.float32)
    out = dg.unpad_vertex_array(
        np.asarray(
            ring_blocked_apply_simulated(
                pair.fwd, jnp.asarray(dg.pad_vertex_array(x))
            )
        )
    )
    np.testing.assert_allclose(
        out, dense @ x.astype(np.float64), rtol=1e-4, atol=1e-4
    )

    # the wire plan only prices the hops that actually happen
    plan = ring_wire_plan(pair.fwd, widths=[5], itemsize=4)
    assert plan["transfers"] == 1
    assert [s["step"] for s in plan["steps"]] == [1]
    assert plan["steps"][0]["bytes"] == dg.vp * 5 * 4
    assert plan["peak_resident_rows"] == 2 * dg.vp


# ---- wire dtype ------------------------------------------------------------


def test_ring_bf16_wire_within_tolerance(rng):
    """WIRE_DTYPE:bf16 rounds each SHIPPED row once (8-bit mantissa) but
    accumulates f32 — the result stays within a bf16-rounding bound of
    the f32 wire."""
    g, dense, dg = _rig(rng, 2, v_num=64, e_num=420)
    pair = RingBlockedPair.build(dg, vt=16)
    x = rng.standard_normal((g.v_num, 9)).astype(np.float32)
    xp = jnp.asarray(dg.pad_vertex_array(x))
    f32 = np.asarray(ring_blocked_apply_simulated(pair.fwd, xp))
    bf16 = np.asarray(
        ring_blocked_apply_simulated(pair.fwd, xp, wire_dtype=jnp.bfloat16)
    )
    scale = np.abs(f32).max()
    assert np.abs(bf16 - f32).max() <= 0.02 * scale
    # but it must NOT be bitwise identical (the wire narrowing is real)
    assert not np.array_equal(bf16, f32)


def test_resolve_wire_dtype_validation(monkeypatch):
    from neutronstarlite_tpu.parallel.ring_schedule import resolve_wire_dtype

    monkeypatch.delenv("NTS_WIRE_DTYPE", raising=False)
    assert resolve_wire_dtype("") is None
    assert resolve_wire_dtype("f32") is None
    assert resolve_wire_dtype("bf16") == jnp.dtype(jnp.bfloat16)
    with pytest.raises(ValueError, match="WIRE_DTYPE"):
        resolve_wire_dtype("fp8")
    # env override wins over the cfg value (launcher parity)
    monkeypatch.setenv("NTS_WIRE_DTYPE", "bf16")
    assert resolve_wire_dtype("f32") == jnp.dtype(jnp.bfloat16)


def test_dist_path_cfg_validation():
    from neutronstarlite_tpu.utils.config import InputInfo

    cfg = InputInfo()
    cfg._apply("DIST_PATH", "ring_blocked")
    assert cfg.dist_path == "ring_blocked"
    with pytest.raises(ValueError, match="DIST_PATH"):
        cfg._apply("DIST_PATH", "ring")
    with pytest.raises(ValueError, match="WIRE_DTYPE"):
        cfg._apply("WIRE_DTYPE", "half")


def test_ring_refused_on_mirror_family_trainers(rng):
    """DIST_PATH:ring_blocked on the GAT / DepCache trainers must refuse
    with an error naming the supported family, not silently ignore."""
    from neutronstarlite_tpu.graph.dataset import GNNDatum
    from neutronstarlite_tpu.models.base import get_algorithm
    from neutronstarlite_tpu.utils.config import InputInfo

    V, E = 40, 200
    src = rng.integers(0, V, size=E, dtype=np.uint32)
    dst = rng.integers(0, V, size=E, dtype=np.uint32)
    datum = GNNDatum.random_generate(V, 6, 3, seed=3)
    for algo in ("GATDIST", "GCNDISTCACHE"):
        cfg = InputInfo()
        cfg.algorithm = algo
        cfg.vertices = V
        cfg.layer_string = "6-8-3"
        cfg.partitions = 2
        cfg.dist_path = "ring_blocked_sim"
        with pytest.raises(ValueError, match="ring_blocked"):
            get_algorithm(algo).from_arrays(cfg, src, dst, datum)


# ---- backward parity through a 2-layer GCN ---------------------------------


def test_ring_trainer_matches_all_gather_trainer(rng):
    """DIST_PATH:ring_blocked_sim vs OPTIM_KERNEL+KERNEL_TILE (the
    all_gather blocked path): the WHOLE loss curve of a 2-layer GCN must
    agree — every epoch's forward AND jax.grad backward went through the
    ring."""
    from neutronstarlite_tpu.graph.dataset import GNNDatum
    from neutronstarlite_tpu.models.base import get_algorithm
    from neutronstarlite_tpu.utils.config import InputInfo

    V, E = 60, 420
    src = rng.integers(0, V, size=E, dtype=np.uint32)
    dst = rng.integers(0, V, size=E, dtype=np.uint32)
    datum = GNNDatum.random_generate(V, 6, 3, seed=3)

    def losses(**kw):
        cfg = InputInfo()
        cfg.algorithm = "GCNDIST"
        cfg.vertices = V
        cfg.layer_string = "6-8-3"
        cfg.epochs = 3
        cfg.learn_rate = 0.01
        cfg.weight_decay = 1e-4
        cfg.decay_epoch = -1
        cfg.drop_rate = 0.0
        cfg.partitions = 2
        for k, v in kw.items():
            setattr(cfg, k, v)
        tr = get_algorithm("GCNDIST").from_arrays(cfg, src, dst, datum)
        tr.run()
        return tr.loss_history

    ring = losses(dist_path="ring_blocked_sim", kernel_tile=16)
    ag = losses(optim_kernel=True, kernel_tile=16)
    assert len(ring) == 3
    np.testing.assert_allclose(ring, ag, rtol=1e-4, atol=1e-5)


# ---- the structural memory claim -------------------------------------------


def _collect_avals(jaxpr, acc):
    for eqn in jaxpr.eqns:
        for v in list(eqn.invars) + list(eqn.outvars):
            aval = getattr(v, "aval", None)
            if aval is not None and hasattr(aval, "shape"):
                acc.add(tuple(aval.shape))
        for p in eqn.params.values():
            j = getattr(p, "jaxpr", None)
            if j is not None:
                _collect_avals(j if hasattr(j, "eqns") else j.jaxpr, acc)
            elif hasattr(p, "eqns"):
                _collect_avals(p, acc)
    return acc


def _shard_map_inner_shapes(fn, arg):
    """All array shapes appearing INSIDE shard_map bodies of fn's jaxpr
    (recursing through custom_vjp / scan sub-jaxprs)."""
    shapes: set = set()

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if "shard_map" in eqn.primitive.name:
                inner = eqn.params.get("jaxpr")
                _collect_avals(
                    inner.jaxpr if hasattr(inner, "jaxpr") else inner, shapes
                )
            else:
                for p in eqn.params.values():
                    j = getattr(p, "jaxpr", None)
                    if j is not None:
                        walk(j if hasattr(j, "eqns") else j.jaxpr)
                    elif hasattr(p, "eqns"):
                        walk(p)

    walk(jax.make_jaxpr(fn)(arg).jaxpr)
    return shapes


def test_ring_jaxpr_has_no_gathered_slab(rng):
    """The acceptance criterion made structural: the ring body never
    materializes a [P*vp, f] array (its largest exchange buffers are the
    two [vp, f] shards), while the all_gather blocked body provably
    does."""
    from neutronstarlite_tpu.parallel.dist_blocked import (
        DistBlockedEllPair,
        dist_blocked_gather_dst_from_src,
    )
    from neutronstarlite_tpu.parallel.dist_ring_blocked import (
        dist_ring_blocked_gather_dst_from_src,
    )
    from neutronstarlite_tpu.parallel.mesh import make_mesh

    P, f = 4, 6
    g, _, dg = _rig(rng, P)
    mesh = make_mesh(P)
    pair_s = RingBlockedPair.build(dg, vt=16).shard(mesh)
    bpair_s = DistBlockedEllPair.build(dg, vt=16).shard(mesh)
    x = jnp.zeros((P * dg.vp, f), jnp.float32)

    ring_shapes = _shard_map_inner_shapes(
        lambda v: dist_ring_blocked_gather_dst_from_src(mesh, pair_s, v), x
    )
    ag_shapes = _shard_map_inner_shapes(
        lambda v: dist_blocked_gather_dst_from_src(mesh, bpair_s, v), x
    )
    slab = (P * dg.vp, f)
    assert slab not in ring_shapes, "ring body materializes the full slab"
    assert (dg.vp, f) in ring_shapes  # the per-shard double buffer IS there
    assert slab in ag_shapes  # the all_gather body really is O(P*vp)


# ---- cfg smoke: ring_step obs accounting (CI/tooling satellite) ------------


@multidevice
def test_ring_smoke_cfg_obs_accounting(tmp_path, monkeypatch, capsys):
    """configs/gcn_dist_ring_smoke.cfg on the CPU sim mesh: the obs
    stream validates, its ring_step bytes sum to the wire_accounting
    prediction, and the residency gauge pins the 2*vp double buffer."""
    from neutronstarlite_tpu.obs import schema
    from neutronstarlite_tpu.run import main as run_main
    from neutronstarlite_tpu.tools.wire_accounting import (
        exchange_rows_per_device,
        peak_resident_rows,
    )

    monkeypatch.setenv("NTS_METRICS_DIR", str(tmp_path))
    rc = run_main([os.path.join(REPO, "configs", "gcn_dist_ring_smoke.cfg")])
    assert rc == 0
    files = sorted(glob.glob(os.path.join(str(tmp_path), "*.jsonl")))
    assert files
    events = [
        json.loads(line) for f in files for line in open(f) if line.strip()
    ]
    assert schema.validate_stream(events) == len(events)

    summ = [e for e in events if e["event"] == "run_summary"][-1]
    P, epochs = 4, 2
    # standard order ships each layer's INPUT width; the input width
    # (1433) rides the ring once, in the input_aggregate phase
    widths, once = [16], [1433]
    rows = summ["gauges"]["wire.rows_per_layer"]
    vp = rows // (P - 1)
    assert rows == exchange_rows_per_device("ring_blocked", P, vp)

    hops = [e for e in events if e["event"] == "ring_step"]
    assert len(hops) == epochs * (P - 1)  # Cora has no empty pairs
    assert all(not h["skipped"] for h in hops)
    predicted = rows * sum(widths) * 4 * epochs
    assert sum(h["bytes"] for h in hops) == predicted
    # and the live counter agrees with the same formula (single source)
    assert summ["counters"]["wire.bytes_fwd"] == predicted
    assert summ["gauges"]["wire.bytes_input_aggregate"] == rows * sum(once) * 4

    # the memory envelope gauge: double buffer, not P shards
    assert summ["gauges"]["wire.peak_resident_rows"] == 2 * vp
    assert summ["gauges"]["wire.peak_resident_rows"] == peak_resident_rows(
        "ring_blocked", P, vp
    )
    # the obs memory collector ran (real stats where the backend has them;
    # explicit nulls on CPU — both prove the collector was consulted)
    assert isinstance(summ["memory"]["available"], bool)

    # the report renders the ring block
    from neutronstarlite_tpu.tools.metrics_report import main as report_main

    rc = report_main([str(tmp_path)])
    out = capsys.readouterr().out
    assert rc == 0
    assert "ring-pipelined exchange:" in out
    assert "#ring_wire_bytes=" in out
    assert "#ring_peak_resident_rows=" in out
