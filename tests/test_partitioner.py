"""2D (vertex x feature) mesh partitioner suite (ISSUE 12), on CPU.

Contracts pinned here:

- MESH cfg/env parsing is loud (the PRECISION-typo lesson) and the
  mesh-shape validation at the funnel names both numbers when the shape
  exceeds the visible device count;
- the logical-axis rules map meaning -> mesh axes (T5X pattern);
- equivalence oracles: a ``(Pv, 1)`` mesh is BITWISE the existing
  ring_blocked/ring_blocked_sim schedule; ``(1, Pf)`` matches the
  single-chip blocked path's loss curve; a ``(2, 2)`` end-to-end
  dist-GCN run has finite decreasing loss and wire gauges equal to
  ``wire_accounting.predict_mesh``'s 2D pricing;
- the collective 2D exchange on a real (virtual-device) mesh is bitwise
  equal to the sim twin, and its shard_map body holds NO full-width
  ``[vp, f]`` aval — every buffer is the ``[vp, f/Pf]`` slab (the
  acceptance criterion made structural);
- the memory claim: ``Pf=2`` halves the peak resident feature bytes of
  the same-Pv 1D layout (the O(vp*f/Pf) math; at equal DEVICE count the
  total per-device bytes match the 1D layout — the 2D win is the slab
  SHAPE, which is what unlocks graphs whose feature rows exceed one
  device — docs/PERF.md);
- tune integration: MESH:auto enumerates the factorizations of the
  device budget, decides, persists, and replays cached with zero
  trials;
- elastic integration: a 2D plan's survivor replan is a MESH RESHAPE
  (typed replan record with from_mesh/to_mesh);
- comm_bench --mesh emits micro_bench-shaped JSON metrics_report --diff
  can gate.
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
from neutronstarlite_tpu.graph.dataset import GNNDatum
from neutronstarlite_tpu.graph.storage import build_graph
from neutronstarlite_tpu.models import get_algorithm
from neutronstarlite_tpu.parallel import partitioner as pmod
from neutronstarlite_tpu.parallel.dist_graph import DistGraph
from neutronstarlite_tpu.parallel.dist_ring_blocked import (
    RingBlockedPair,
    ring_blocked_apply_simulated,
)
from neutronstarlite_tpu.parallel.mesh import (
    FEATURE_AXIS,
    VERTEX_AXIS,
    make_mesh2d,
    validate_mesh_request,
)
from neutronstarlite_tpu.tools.wire_accounting import predict_mesh
from neutronstarlite_tpu.utils.config import InputInfo

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

multidevice = pytest.mark.skipif(
    os.environ.get("NTS_MULTIDEVICE", "1") == "0",
    reason="XLA:CPU collectives starve on a single-core host",
)


# ---- MESH value + shape validation ------------------------------------------


def test_mesh_cfg_parse_and_validation():
    cfg = InputInfo()
    cfg._apply("MESH", "2,2")
    assert cfg.mesh == "2,2"
    cfg._apply("MESH", "4x2")  # the x spelling canonicalizes
    assert cfg.mesh == "4,2"
    cfg._apply("MESH", "auto")
    assert cfg.mesh == "auto"
    for bad in ("2", "2,0", "a,b", "2,2,2"):
        with pytest.raises(ValueError, match="MESH"):
            cfg._apply("MESH", bad)
    spec = pmod.MeshSpec.parse("2,2")
    assert (spec.pv, spec.pf, spec.devices) == (2, 2, 4)
    assert spec.label() == "2x2" and spec.cfg_value() == "2,2"


def test_mesh_shape_validation_names_both_numbers():
    """A shape exceeding the visible device count dies with ONE line
    naming the requested product and the rig's count — not a deep
    shard_map trace (the 8-virtual-device rig, conftest)."""
    validate_mesh_request(2, 2)  # fits
    with pytest.raises(ValueError, match=r"16 devices but only 8"):
        validate_mesh_request(4, 4)
    with pytest.raises(ValueError, match="axes must be >= 1"):
        validate_mesh_request(0, 2)
    m = make_mesh2d(2, 2)
    assert m.shape == {VERTEX_AXIS: 2, FEATURE_AXIS: 2}


def test_logical_axis_rules():
    assert pmod.logical_to_mesh_axes(("vertex", "feature")) == (
        VERTEX_AXIS, FEATURE_AXIS,
    )
    assert pmod.logical_to_mesh_axes(("vertex", None)) == (VERTEX_AXIS, None)
    assert pmod.logical_to_mesh_axes(("replicated",)) == (None,)
    with pytest.raises(ValueError, match="unknown logical axis"):
        pmod.logical_to_mesh_axes(("vertx",))


def test_slab_and_padding_helpers():
    assert pmod.slab_width(1433, 2) == 717
    assert pmod.padded_width(1433, 2) == 1434
    assert pmod.slab_width(16, 2) == 8 and pmod.padded_width(16, 2) == 16
    assert pmod.slab_width(7, 1) == 7
    a = np.ones((4, 7), np.float32)
    p = pmod.pad_feature_cols(a, 2)
    assert p.shape == (4, 8) and (p[:, 7] == 0).all()
    assert pmod.pad_feature_cols(a, 1) is a


def test_check_mesh_cfg_refusals():
    cfg = InputInfo()
    cfg.mesh = "2,2"
    cfg.dist_path = "all_gather"
    with pytest.raises(ValueError, match="ring"):
        pmod.check_mesh_cfg(cfg)
    cfg.dist_path = ""
    cfg.optim_kernel = True
    with pytest.raises(ValueError, match="OPTIM_KERNEL"):
        pmod.check_mesh_cfg(cfg)
    cfg.optim_kernel = False
    cfg.comm_layer = "mirror"
    with pytest.raises(ValueError, match="COMM_LAYER"):
        pmod.check_mesh_cfg(cfg)
    cfg.comm_layer = "auto"
    cfg.partitions = 3
    with pytest.raises(ValueError, match="PARTITIONS:3"):
        pmod.check_mesh_cfg(cfg)
    cfg.partitions = 4
    pmod.check_mesh_cfg(cfg)  # consistent: no raise


def test_mesh_refused_on_non_dist_trainers(rng):
    """MESH on a family without a feature-shardable exchange refuses at
    the funnel naming the supported family (the DIST_PATH pattern)."""
    V, E = 40, 200
    src = rng.integers(0, V, size=E, dtype=np.uint32)
    dst = rng.integers(0, V, size=E, dtype=np.uint32)
    datum = GNNDatum.random_generate(V, 6, 3, seed=3)
    cfg = InputInfo()
    cfg.algorithm = "GCNCPU"
    cfg.vertices = V
    cfg.layer_string = "6-8-3"
    cfg.mesh = "2,2"
    with pytest.raises(ValueError, match="MESH"):
        get_algorithm("GCNCPU").from_arrays(cfg, src, dst, datum)


# ---- trainer-level equivalence oracles --------------------------------------


def _planted(rng, V=60, E=420, f=11, C=3):
    src = rng.integers(0, V, size=E, dtype=np.uint32)
    dst = rng.integers(0, V, size=E, dtype=np.uint32)
    datum = GNNDatum.random_generate(V, f, C, seed=3)
    g = build_graph(src, dst, V, weight="gcn_norm")
    return src, dst, datum, g


def _run_dist(src, dst, datum, g, f=11, C=3, epochs=3, algo="GCNDIST",
              **kw):
    cfg = InputInfo()
    cfg.algorithm = algo
    cfg.vertices = int(datum.feature.shape[0])
    cfg.layer_string = f"{f}-8-{C}"
    cfg.epochs = epochs
    cfg.decay_epoch = -1
    cfg.drop_rate = 0.0
    for k, v in kw.items():
        setattr(cfg, k, v)
    tr = get_algorithm(algo).from_arrays(cfg, src, dst, datum, host_graph=g)
    tr.run()
    return tr


def test_pv1_mesh_is_bitwise_the_ring_blocked_sim(rng):
    """(Pv, 1): the partitioner emits EXACTLY the existing ring_blocked
    schedule — whole loss curves bitwise equal, not approx."""
    src, dst, datum, g = _planted(rng)
    a = _run_dist(src, dst, datum, g, mesh="2,1",
                  dist_path="ring_blocked_sim", kernel_tile=16)
    b = _run_dist(src, dst, datum, g, partitions=2,
                  dist_path="ring_blocked_sim", kernel_tile=16)
    assert a.loss_history == b.loss_history


def test_1xpf_mesh_matches_single_chip_blocked_loss_curve(rng):
    """(1, Pf): no vertex ring at all — the loss curve must match the
    single-chip blocked path (OPTIM_KERNEL + KERNEL_TILE) to float
    tolerance (the feature-slab partial-sum order differs)."""
    src, dst, datum, g = _planted(rng)
    a = _run_dist(src, dst, datum, g, mesh="1,2",
                  dist_path="ring_blocked_sim", kernel_tile=16)
    cfg = InputInfo()
    cfg.algorithm = "GCNCPU"
    cfg.vertices = 60
    cfg.layer_string = "11-8-3"
    cfg.epochs = 3
    cfg.decay_epoch = -1
    cfg.drop_rate = 0.0
    cfg.optim_kernel = True
    cfg.kernel_tile = 16
    sc = get_algorithm("GCNCPU").from_arrays(cfg, src, dst, datum,
                                             host_graph=g)
    sc.run()
    np.testing.assert_allclose(a.loss_history, sc.loss_history,
                               rtol=1e-4, atol=1e-5)


def test_2x2_end_to_end_loss_and_gauges_match_predict_mesh(rng):
    """The (2, 2) acceptance run on the sim twin: finite decreasing
    loss, mesh.* gauges present, and every live wire counter equal to
    predict_mesh's 2D pricing (single slab_width definition)."""
    src, dst, datum, g = _planted(rng)
    tr = _run_dist(src, dst, datum, g, mesh="2,2",
                   dist_path="ring_blocked_sim", kernel_tile=16)
    losses = tr.loss_history
    assert all(np.isfinite(losses)) and losses[-1] < losses[0]
    snap = tr.metrics.snapshot()
    gauges, counters = snap["gauges"], snap["counters"]
    assert gauges["mesh.shape"] == "2x2"
    assert (gauges["mesh.pv"], gauges["mesh.pf"]) == (2, 2)
    # the input width (11) rides the ring once, in the input_aggregate
    # phase; an epoch ships the hidden width alone. The residency gauges
    # are the run's peaks and hold the wider, one-off exchange
    pred = predict_mesh(g, 2, 2, [8], itemsize=4)
    once = predict_mesh(g, 2, 2, [11], itemsize=4)
    assert gauges["mesh.slab_cols"] == sum(pred["slab_widths"])
    assert gauges["wire.peak_resident_rows"] == pred["peak_resident_rows"]
    assert gauges["wire.peak_resident_feature_bytes"] == once[
        "peak_resident_feature_bytes"
    ]
    assert counters["wire.bytes_fwd"] == pred["bytes_per_epoch"] * 3
    assert gauges["wire.bytes_input_aggregate"] == once["bytes_per_epoch"]
    # bf16 wire rides the 2D ring too
    tb = _run_dist(src, dst, datum, g, mesh="2,2",
                   dist_path="ring_blocked_sim", kernel_tile=16,
                   wire_dtype="bf16")
    assert all(np.isfinite(tb.loss_history))
    assert tb.metrics.snapshot()["counters"]["wire.bytes_fwd"] == \
        predict_mesh(g, 2, 2, [8], itemsize=2)["bytes_per_epoch"] * 3


@multidevice
def test_2d_collective_trainer_matches_sim_twin(rng):
    """The REAL (2, 2) mesh (virtual CPU devices): collective 2D
    training — slab-sharded ring + GSPMD feature all-reduce at the
    contraction — matches the sim twin's loss curve."""
    src, dst, datum, g = _planted(rng)
    sim = _run_dist(src, dst, datum, g, mesh="2,2",
                    dist_path="ring_blocked_sim", kernel_tile=16)
    real = _run_dist(src, dst, datum, g, mesh="2,2",
                     dist_path="ring_blocked", kernel_tile=16)
    np.testing.assert_allclose(real.loss_history, sim.loss_history,
                               rtol=1e-4, atol=1e-5)


# ---- the collective 2D exchange: bitwise + structural -----------------------


@multidevice
def test_2d_exchange_bitwise_and_no_full_width_aval(rng):
    """The 2D shard_map ring on a real (2, 2) mesh is BITWISE equal to
    the collective-free sim (the aggregation is feature-column-
    independent), and its body holds NO [vp, f] full-width aval — every
    buffer is the [vp, f/Pf] slab. The same body on a (2, 1) mesh DOES
    hold [vp, f]: the acceptance's halving, made structural."""
    from jax.sharding import NamedSharding, PartitionSpec as PS

    from neutronstarlite_tpu.parallel.dist_ring_blocked import (
        dist_ring2d_gather_dst_from_src,
    )
    from tests.test_dist_ring import _shard_map_inner_shapes

    pv, pf, f = 2, 2, 10
    g, dense = tiny_graph(rng, v_num=64, e_num=420)
    dg = DistGraph.build(g, pv, edge_chunk=64)
    pair = RingBlockedPair.build(dg, vt=16)
    mesh = make_mesh2d(pv, pf)
    pair_s = pair.shard(mesh, axis=VERTEX_AXIS)
    x = rng.standard_normal((g.v_num, f)).astype(np.float32)
    xp = dg.pad_vertex_array(x)
    xs = jax.device_put(
        jnp.asarray(xp), NamedSharding(mesh, PS(VERTEX_AXIS, FEATURE_AXIS))
    )
    real = np.asarray(
        dist_ring2d_gather_dst_from_src(mesh, pair_s, xs, pf=pf)
    )
    sim = np.asarray(
        ring_blocked_apply_simulated(pair.fwd, jnp.asarray(xp))
    )
    assert np.array_equal(real, sim)
    # ...and the dense golden
    np.testing.assert_allclose(
        dg.unpad_vertex_array(real), dense @ x.astype(np.float64),
        rtol=1e-4, atol=1e-4,
    )

    # structural: the 2D body sees only the slab
    shapes_2d = _shard_map_inner_shapes(
        lambda v: dist_ring2d_gather_dst_from_src(mesh, pair_s, v, pf=pf),
        xs,
    )
    assert (dg.vp, f) not in shapes_2d, "2D body materializes full width"
    assert (dg.vp, f // pf) in shapes_2d  # the slab double buffer IS there

    mesh1 = make_mesh2d(pv, 1)
    pair_1 = pair.shard(mesh1, axis=VERTEX_AXIS)
    shapes_1d = _shard_map_inner_shapes(
        lambda v: dist_ring2d_gather_dst_from_src(mesh1, pair_1, v, pf=1),
        jnp.asarray(xp),
    )
    assert (dg.vp, f) in shapes_1d  # the (Pv, 1) layout is full-width


def test_memory_claim_pf_halves_the_resident_slab(rng):
    """The O(vp * f/Pf) math as numbers: at FIXED Pv, Pf=2 halves the
    peak resident feature bytes (exactly, for an even width); at equal
    device count the per-device bytes match the 1D layout — the 2D win
    there is the slab SHAPE (rows x half-width), which is what unlocks
    feature rows wider than one device."""
    g, _ = tiny_graph(rng, v_num=96, e_num=700)
    f = 32
    p21 = predict_mesh(g, 2, 1, [f])
    p22 = predict_mesh(g, 2, 2, [f])
    assert p22["peak_resident_feature_bytes"] * 2 == \
        p21["peak_resident_feature_bytes"]
    assert p22["bytes_per_epoch"] * 2 == p21["bytes_per_epoch"]
    # equal-device-count comparison (the (4,1) baseline): same rows*cols
    # budget, half the column width per device
    p41 = predict_mesh(g, 4, 1, [f])
    assert p22["slab_widths"][0] * 2 == p41["slab_widths"][0]
    assert p22["slab_widths"][0] == f // 2
    assert p41["slab_widths"][0] == f
    # the all-reduce term prices the contraction a (1, P) mesh pays
    p14 = predict_mesh(g, 1, 4, [f])
    assert p14["bytes_per_epoch"] == 0  # no vertex ring at all
    assert p14["allreduce_bytes_per_epoch"] > 0  # ...but not wire-free


def test_predict_mesh_matches_hand_formula(rng):
    g, _ = tiny_graph(rng, v_num=60, e_num=400)
    pred = predict_mesh(g, 2, 2, [11, 8], itemsize=4)
    vp = pred["vp"]
    assert pred["slab_widths"] == [6, 4]
    assert pred["exchange_rows"] == (2 - 1) * vp
    assert pred["bytes_per_epoch"] == vp * (6 + 4) * 4
    assert pred["peak_resident_rows"] == 2 * vp
    assert pred["peak_resident_feature_bytes"] == 2 * vp * 6 * 4
    # predict_all exposes the same entry as strategy ring2d
    from neutronstarlite_tpu.tools.wire_accounting import predict_all

    out = predict_all(g, 4, 11, widths=[11, 8], mesh=(2, 2))
    assert out["strategies"]["ring2d"] == pred


# ---- tune integration -------------------------------------------------------


def test_mesh_auto_enumerates_factorizations():
    from neutronstarlite_tpu.tune import space

    cls = get_algorithm("GCNDIST")
    cfg = InputInfo()
    cfg.algorithm = "GCNDIST"
    cfg.layer_string = "8-8-3"
    cfg.partitions = 4
    cfg.dist_path = "ring_blocked_sim"
    cfg.mesh = "auto"
    cands = space.enumerate_candidates(cls, cfg, 4, simulate=True)
    meshes = {c.mesh for c in cands}
    # '' (legacy 1D) + the Pf>1 factorizations; never the (P, 1)
    # duplicate of ''
    assert meshes == {"", "2,2", "1,4"}
    labels = [c.label() for c in cands]
    assert "ring_blocked_sim|-|-|-|2,2|-" in labels


def test_mesh_auto_resolution_and_cached_replay(tmp_path, monkeypatch, rng):
    monkeypatch.setenv("NTS_TUNE", "measure")
    monkeypatch.setenv("NTS_TUNE_DIR", str(tmp_path / "cache"))
    monkeypatch.setenv("NTS_METRICS_DIR", str(tmp_path / "obs"))
    src, dst, datum, g = _planted(rng, f=8)
    kw = dict(mesh="auto", dist_path="ring_blocked_sim", kernel_tile=16,
              partitions=4, epochs=2)
    tr = _run_dist(src, dst, datum, g, f=8, **kw)
    assert tr.cfg.mesh in ("", "2,2", "1,4")  # concrete after resolution
    evs = []
    for p in sorted(glob.glob(str(tmp_path / "obs" / "*.jsonl"))):
        evs.extend(json.loads(l) for l in open(p) if l.strip())
    d = [e for e in evs if e["event"] == "tune_decision"]
    assert len(d) == 1 and d[0]["source"] == "measured"
    assert "mesh" in d[0]["decision"]
    trials = [e for e in evs if e["event"] == "tune_trial"]
    assert {t["candidate"] for t in trials} >= {
        "ring_blocked_sim|-|-|-|2,2|-"
    }
    # cached replay: identical decision, zero trials
    monkeypatch.setenv("NTS_TUNE", "cached")
    monkeypatch.setenv("NTS_METRICS_DIR", str(tmp_path / "obs2"))
    tr2 = _run_dist(src, dst, datum, g, f=8, **kw)
    evs2 = []
    for p in sorted(glob.glob(str(tmp_path / "obs2" / "*.jsonl"))):
        evs2.extend(json.loads(l) for l in open(p) if l.strip())
    assert not [e for e in evs2 if e["event"] == "tune_trial"]
    d2 = [e for e in evs2 if e["event"] == "tune_decision"]
    assert d2[0]["source"] == "cached"
    assert d2[0]["candidate"] == d[0]["candidate"]
    assert tr2.cfg.mesh == tr.cfg.mesh


def test_nts_mesh_env_folds_through_the_funnel(rng, monkeypatch):
    """NTS_MESH launcher parity: the env spelling lands in cfg.mesh at
    the funnel head and gets the same validation the cfg key would."""
    src, dst, datum, g = _planted(rng)
    monkeypatch.setenv("NTS_MESH", "2x2")
    tr = _run_dist(src, dst, datum, g, dist_path="ring_blocked_sim",
                   kernel_tile=16, epochs=2)
    assert tr.cfg.mesh == "2,2"
    assert tr.metrics.snapshot()["gauges"]["mesh.shape"] == "2x2"


# ---- elastic: replan as mesh reshape ----------------------------------------


def test_elastic_replan_is_a_mesh_reshape(rng, tmp_path, monkeypatch):
    from neutronstarlite_tpu.resilience import elastic

    monkeypatch.setenv("NTS_METRICS_DIR", str(tmp_path / "obs"))
    src, dst, datum, g = _planted(rng)
    tr = _run_dist(src, dst, datum, g, mesh="2,2",
                   dist_path="ring_blocked_sim", kernel_tile=16, epochs=1)
    assert tr.mesh_spec.devices == 4
    try:
        new_p = elastic.replan_survivors(tr, lost_partition=1)
    finally:
        elastic.reset()
    # 4 devices -> 3: the reshape re-emitted a 3-device shape
    assert tr.mesh_spec is not None and tr.mesh_spec.devices == 3
    assert new_p == tr.mesh_spec.pv
    evs = []
    for p in sorted(glob.glob(str(tmp_path / "obs" / "*.jsonl"))):
        evs.extend(json.loads(l) for l in open(p) if l.strip())
    replans = [e for e in evs if e["event"] == "replan"]
    assert replans
    r = replans[-1]
    assert r["from_mesh"] == "2x2"
    assert r["to_mesh"] == tr.mesh_spec.label()
    from neutronstarlite_tpu.obs import schema

    schema.validate_stream(replans)
    # the reshaped plan still trains
    tr.run()
    assert all(np.isfinite(tr.loss_history))


def test_2d_checkpoint_restores_across_layouts(rng, tmp_path):
    """Checkpoints store UNPADDED param shapes: a (2,2) run's checkpoint
    (feature width 11 padded to 12 in-model) restores into the 1D layout
    — the elastic reshape's restore path, and layout portability in
    general."""
    src, dst, datum, g = _planted(rng)
    ck = str(tmp_path / "ck")
    a = _run_dist(src, dst, datum, g, mesh="2,2",
                  dist_path="ring_blocked_sim", kernel_tile=16, epochs=2,
                  checkpoint_dir=ck, checkpoint_every=1)
    assert len(a.loss_history) == 2
    # restore into the 1D layout: epochs 1..2 replay there, no pad-row
    # shape mismatch
    b = _run_dist(src, dst, datum, g, partitions=2,
                  dist_path="ring_blocked_sim", kernel_tile=16, epochs=3,
                  checkpoint_dir=ck, checkpoint_every=1)
    assert len(b.loss_history) == 1  # resumed at epoch 2, ran epoch 2 only
    # ...and back into a 2D layout
    c = _run_dist(src, dst, datum, g, mesh="2,2",
                  dist_path="ring_blocked_sim", kernel_tile=16, epochs=4,
                  checkpoint_dir=ck, checkpoint_every=1)
    assert len(c.loss_history) == 1
    assert all(np.isfinite(c.loss_history))


# ---- comm_bench --mesh ------------------------------------------------------


def test_comm_bench_mesh_leg_and_diff(tmp_path, capsys):
    from neutronstarlite_tpu.parallel.comm_bench import main as bench_main
    from neutronstarlite_tpu.tools.metrics_report import main as report_main

    for side, path in (("1d", "a.json"), ("2d", "b.json")):
        rc = bench_main([
            "--vertices", "400", "--avg-degree", "6", "--feature", "8",
            "--mesh", "2,2", "--steps", "2", "--side", side,
        ])
        out = capsys.readouterr().out
        assert rc == 0
        obj = json.loads(out)
        assert "platform" in obj and set(obj["ops"]) == {
            f"mesh_exchange_{side}"
        }
        op = obj["ops"][f"mesh_exchange_{side}"]
        assert op["ms"] >= 0 and "wire_bytes_per_dev_layer" in op
        (tmp_path / path).write_text(out)
    # the _1d/_2d suffixes canonicalize to ONE shared diff key
    rc = report_main([
        "--diff", str(tmp_path / "a.json"), str(tmp_path / "b.json"),
        "--tol", "100.0",
    ])
    out = capsys.readouterr().out
    assert rc == 0
    assert "micro.mesh_exchange_ms" in out


# ---- cfg smoke (the MESH_GATE's pytest twin, tiny scale) --------------------


def test_mesh_smoke_cfg_parses_and_is_consistent():
    cfg = InputInfo.read_from_cfg_file(
        os.path.join(REPO, "configs", "gcn_dist_mesh_smoke.cfg")
    )
    assert cfg.mesh == "2,2"
    assert cfg.dist_path == "ring_blocked_sim"
    pmod.check_mesh_cfg(cfg)  # PARTITIONS:4 agrees with 2x2
