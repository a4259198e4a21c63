"""On-hardware TPU coverage.

tests/conftest.py pins the whole pytest process to the CPU platform, so
TPU checks run in ONE subprocess (backend init is seconds; one process
amortizes it across all checks; the pytest parent holds only the CPU, so
the child can have the chip) with JAX_PLATFORMS removed from its
environment: JAX then takes the TPU when the machine has one and the CPU
otherwise. The subprocess computes golden results with numpy on the host
and runs the core ops on the device:

- ``gather_dst_from_src`` on both backends (chunked sorted-scatter and ELL
  gather) vs the dense [V, V] @ [V, f] golden, f32 and bf16 — the open
  round-1 question was exactly how XLA's scatter/gather lower on real TPU;
- the edge-op chain (scatter_src_to_edge -> edge_softmax ->
  aggregate_edge_to_dst) vs a dense softmax golden;
- a short GCN training run asserting the loss decreases on-device.

On a machine without a TPU the child reports the CPU within seconds and
the module skips. On the chip nothing skips: a backend that fails to start,
a bsp kernel that fails to lower, a crash or a hang all fail the tests.
Run it there with ``chiprun -- python -m pytest tests/test_tpu.py -q``.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

_TPU_SRC = r"""
import json, sys
import numpy as np

import jax
import jax.numpy as jnp

platform = jax.default_backend()
if platform == "cpu":
    from neutronstarlite_tpu.utils.platform import tpu_chip_nodes
    if tpu_chip_nodes():
        # the machine has a chip and JAX fell back to the CPU: the backend
        # failed to start, which is a failure, not a machine without a TPU
        sys.exit(f"{tpu_chip_nodes()} TPU chip(s) on this host but JAX "
                 "reports the cpu backend")
    print(json.dumps({"skip": "no accelerator (default backend is cpu)"}))
    sys.exit(0)

from neutronstarlite_tpu.graph.storage import build_graph
from neutronstarlite_tpu.ops.device_graph import DeviceGraph
from neutronstarlite_tpu.ops.aggregate import gather_dst_from_src, gather_src_from_dst
from neutronstarlite_tpu.ops.ell import EllPair
from neutronstarlite_tpu.ops.edge import (
    scatter_src_to_edge, edge_softmax, aggregate_edge_to_dst_weighted,
)

rng = np.random.default_rng(7)
V, E, F = 257, 2111, 64
src = rng.integers(0, V, size=E, dtype=np.uint32)
dst = rng.integers(0, V, size=E, dtype=np.uint32)
loops = np.arange(V, dtype=np.uint32)
src = np.concatenate([src, loops]); dst = np.concatenate([dst, loops])
g = build_graph(src, dst, V, weight="gcn_norm")
dg = DeviceGraph.from_host(g, edge_chunk=512)  # force the multi-chunk scan
ell = EllPair.from_host(g)

from neutronstarlite_tpu.graph.storage import gcn_norm_weights
w = gcn_norm_weights(src, dst, g.out_degree, g.in_degree).astype(np.float64)
dense = np.zeros((V, V))
np.add.at(dense, (dst.astype(np.int64), src.astype(np.int64)), w)

x = rng.standard_normal((V, F)).astype(np.float32)
golden = dense @ x.astype(np.float64)

out = {"platform": platform, "device": str(jax.devices()[0]), "checks": {}}

def rel_err(a, b):
    return float(np.abs(np.asarray(a, np.float64) - b).max()
                 / max(np.abs(b).max(), 1e-12))

for name, graph in [("scatter", dg), ("ell", ell)]:
    for dname, xx in [("f32", x), ("bf16", x.astype(jnp.bfloat16))]:
        fwd = jax.jit(lambda gr, v: gather_dst_from_src(gr, v))
        r = np.asarray(fwd(graph, jnp.asarray(xx)), np.float64)
        out["checks"][f"agg_{name}_{dname}"] = rel_err(r, golden)

# backward direction (CSR) vs dense transpose
bwd = jax.jit(lambda gr, v: gather_src_from_dst(gr, v))
r = np.asarray(bwd(dg, jnp.asarray(x)), np.float64)
out["checks"]["agg_csr_f32"] = rel_err(r, dense.T @ x.astype(np.float64))

# gradient pairing: d/dx sum(agg(x) * c) == agg_transpose(c)
c = rng.standard_normal((V, F)).astype(np.float32)
gfn = jax.jit(jax.grad(lambda v: (gather_dst_from_src(dg, v) * c).sum()))
r = np.asarray(gfn(jnp.asarray(x)), np.float64)
out["checks"]["agg_grad_f32"] = rel_err(r, dense.T @ c.astype(np.float64))

# edge-op chain: per-dst softmax of edge scores, then weighted aggregate
score = scatter_src_to_edge(dg, jnp.asarray(x[:, :1]))
alpha = jax.jit(lambda s: edge_softmax(dg, s))(score)
agg = jax.jit(lambda a, v: aggregate_edge_to_dst_weighted(dg, a, v))(
    alpha, jnp.asarray(x))
exp = np.zeros((V, V))
sc = x[src.astype(np.int64), 0]
np.add.at(exp, (dst.astype(np.int64), src.astype(np.int64)), np.exp(sc))
den = exp.sum(axis=1, keepdims=True); den[den == 0] = 1.0
soft = exp / den
out["checks"]["edge_softmax_agg"] = rel_err(np.asarray(agg, np.float64),
                                            soft @ x.astype(np.float64))

# fused ELL-GAT attention on hardware: the scatter-free score/softmax/
# aggregate chain must match the edge-op chain's layer output and gradient
from neutronstarlite_tpu.models.gat import gat_layer, gat_layer_ell, init_gat_params
from neutronstarlite_tpu.ops.ell_gat import GatEllPair
g_ones = build_graph(src, dst, V, weight="ones")
dg_ones = DeviceGraph.from_host(g_ones, edge_chunk=512)
gep = GatEllPair.from_host(g_ones)
gat_params = init_gat_params(jax.random.PRNGKey(5), [F, 32])
W_g, a_g = gat_params[0]["W"], gat_params[0]["a"]
want_gat = np.asarray(
    jax.jit(lambda W, a, v: gat_layer(dg_ones, W, a, v, True))(W_g, a_g, jnp.asarray(x)),
    np.float64,
)
got_gat = np.asarray(
    jax.jit(lambda W, a, v: gat_layer_ell(gep, W, a, v, True))(W_g, a_g, jnp.asarray(x)),
    np.float64,
)
out["checks"]["gat_fused_fwd"] = rel_err(got_gat, want_gat)
gw = jax.jit(jax.grad(lambda v: (gat_layer(dg_ones, W_g, a_g, v, True) * c[:, :32]).sum()))(jnp.asarray(x))
fw = jax.jit(jax.grad(lambda v: (gat_layer_ell(gep, W_g, a_g, v, True) * c[:, :32]).sum()))(jnp.asarray(x))
out["checks"]["gat_fused_grad"] = rel_err(np.asarray(fw, np.float64), np.asarray(gw, np.float64))

# blocked (source-tiled) ELL layout on hardware: the beyond-VMEM production
# candidate must agree with the dense golden, forward and gradient
from neutronstarlite_tpu.ops.blocked_ell import BlockedEllPair
bpair = BlockedEllPair.from_host(g, vt=64)
r = np.asarray(jax.jit(gather_dst_from_src)(bpair, jnp.asarray(x)), np.float64)
out["checks"]["agg_blocked_f32"] = rel_err(r, golden)
bgrad = jax.jit(jax.grad(
    lambda v: (gather_dst_from_src(bpair, v) * c).sum()))
r = np.asarray(bgrad(jnp.asarray(x)), np.float64)
out["checks"]["blocked_grad_f32"] = rel_err(r, dense.T @ c.astype(np.float64))

# round 3 — streamed block-sparse kernel (ops/bsp_ell.py): Mosaic compile
# of the scalar-prefetch grid + one-hot MXU combine. A lowering failure is
# recorded so that the bsp tests (and only they) FAIL on it; a post-compile
# crash propagates and fails the module.
from neutronstarlite_tpu.ops.bsp_ell import BspEllPair, bsp_gather_dst_from_src
bsp_pair = BspEllPair.from_host(g, dt=64, vt=128, k_slots=8, r_rows=128)
bfn = jax.jit(bsp_gather_dst_from_src)
try:
    bcompiled = bfn.lower(bsp_pair, jnp.asarray(x)).compile()
except Exception as e:  # noqa: BLE001 — reported per test, see above
    bcompiled = None
    out["bsp"] = f"lowering failed: {type(e).__name__}: {str(e)[:300]}"
if bcompiled is not None:
    r = np.asarray(bcompiled(bsp_pair, jnp.asarray(x)), np.float64)
    out["checks"]["bsp_f32"] = rel_err(r, golden)
    out["bsp"] = "compiled"
    bspg = jax.jit(jax.grad(
        lambda v: (bsp_gather_dst_from_src(bsp_pair, v) * c).sum()))
    r = np.asarray(bspg(jnp.asarray(x)), np.float64)
    out["checks"]["bsp_grad_f32"] = rel_err(r, dense.T @ c.astype(np.float64))
    # round 4 — bf16 slab parity: production rounds the one-hot W entries
    # to the slab dtype (bf16) for the main MXU dot (ops/bsp_ell.py
    # numeric policy); quantify that rounding on chip against the f64
    # golden — same tolerance class as the XLA bf16 aggregation checks.
    # Guarded like the f32 compile: a dtype-specific lowering failure is
    # recorded, never a module-killing crash
    try:
        r = np.asarray(bfn(bsp_pair, jnp.asarray(x, jnp.bfloat16)), np.float64)
        out["checks"]["bsp_bf16"] = rel_err(r, golden)
    except Exception as e:  # noqa: BLE001
        out["bsp_bf16_error"] = f"{type(e).__name__}: {str(e)[:300]}"
    # round 4 — SMEM-budget grid segmentation on chip: a budget of 8
    # splits this graph's 16-block table (the 16-block build fits a
    # 16-block cap in one segment); the per-segment calls must agree
    # with the golden. Restore any rig-level budget setting afterwards.
    import os as _os_seg
    _prior_cap = _os_seg.environ.get("NTS_BSP_MAX_BLOCKS")
    _os_seg.environ["NTS_BSP_MAX_BLOCKS"] = "8"
    try:
        seg_pair = BspEllPair.from_host(g, dt=64, vt=128, k_slots=8, r_rows=128)
    finally:
        if _prior_cap is None:
            _os_seg.environ.pop("NTS_BSP_MAX_BLOCKS", None)
        else:
            _os_seg.environ["NTS_BSP_MAX_BLOCKS"] = _prior_cap
    out["bsp_segments"] = int(seg_pair.fwd.n_seg)
    if seg_pair.fwd.n_seg > 1:
        try:
            r = np.asarray(
                jax.jit(bsp_gather_dst_from_src)(seg_pair, jnp.asarray(x)),
                np.float64,
            )
            out["checks"]["bsp_seg_f32"] = rel_err(r, golden)
        except Exception as e:  # noqa: BLE001
            out["bsp_seg_error"] = f"{type(e).__name__}: {str(e)[:300]}"

# round 3 — dist-bsp on real hardware with ONE chip: a P=1 mesh runs the
# full shard_map + rectangular Mosaic kernel + feature-chunking machinery
# (parallel/dist_bsp.py) — the closest on-chip evidence for the PALLAS:1
# dist path this 1-chip rig can produce
if bcompiled is not None:
    from jax.sharding import Mesh as _Mesh
    from neutronstarlite_tpu.parallel.dist_bsp import (
        DistBspPair, dist_bsp_gather_dst_from_src,
    )
    from neutronstarlite_tpu.parallel.dist_graph import DistGraph
    from neutronstarlite_tpu.parallel.mesh import PARTITION_AXIS

    dgr = DistGraph.build(g, 1, edge_chunk=512)
    dpair = DistBspPair.build(dgr, vt=128)
    mesh1 = _Mesh(np.array(jax.devices()[:1]), (PARTITION_AXIS,))
    dpair_s = dpair.shard(mesh1)
    xp = jnp.asarray(dgr.pad_vertex_array(x))
    r = dgr.unpad_vertex_array(np.asarray(
        jax.jit(lambda v: dist_bsp_gather_dst_from_src(mesh1, dpair_s, v))(xp),
        np.float64,
    ))
    out["checks"]["dist_bsp_p1_f32"] = rel_err(r, golden)

# round 5 — SEGMENTED dist-bsp through the real shard_map on chip: a block
# budget of 8 with 64-row dst tiles cuts this graph's shard into 3 (fwd) and
# 2 (bwd) segments (at the default 512-row tile it is one tile and cannot
# segment), so the uniform menu re-lay + first_tile placement machinery
# (parallel/dist_bsp.py) executes on hardware, P=1 mesh.
if bcompiled is not None:
    import os as _os5
    _os5.environ["NTS_BSP_MAX_BLOCKS"] = "8"
    _os5.environ["NTS_BSP_DT"] = "64"
    try:
        seg_dpair = DistBspPair.build(dgr, vt=128)
        out["dist_bsp_segments"] = int(seg_dpair.fwd.n_seg)
        if seg_dpair.fwd.n_seg > 1:
            seg_dpair_s = seg_dpair.shard(mesh1)
            r = dgr.unpad_vertex_array(np.asarray(
                jax.jit(lambda v: dist_bsp_gather_dst_from_src(
                    mesh1, seg_dpair_s, v))(xp),
                np.float64,
            ))
            out["checks"]["dist_bsp_segmented_f32"] = rel_err(r, golden)
    except Exception as e:  # noqa: BLE001
        out["dist_bsp_segmented_error"] = f"{type(e).__name__}: {str(e)[:300]}"
    finally:
        _os5.environ.pop("NTS_BSP_MAX_BLOCKS", None)
        _os5.environ.pop("NTS_BSP_DT", None)

# round 5 — SplitMirror fused aggregation on chip (remote-only exchange +
# resident local edges), P=1 mesh: the all_to_all is a self-copy but the
# whole two-source gather/segsum machinery runs on device
from neutronstarlite_tpu.parallel.mirror import SplitMirror
from neutronstarlite_tpu.parallel.dist_edge_ops import (
    dist_gather_dst_from_src_mirror_split,
)
sm1 = SplitMirror.build(g, 1)
sm1_t = sm1.shard(mesh1)
xs1 = jnp.asarray(sm1.pad_vertex_array(x))
r = sm1.unpad_vertex_array(np.asarray(
    jax.jit(lambda v: dist_gather_dst_from_src_mirror_split(
        mesh1, sm1, sm1_t, v))(xs1),
    np.float64,
))
out["checks"]["split_mirror_f32"] = rel_err(r, golden)

# round 5 — chunked + remat'd gated edge chain on chip (GAT shape:
# width-1 score), multi-chunk forced, P=1 mesh
from neutronstarlite_tpu.parallel.mirror import MirrorGraph, chunk_edge_list
from neutronstarlite_tpu.parallel.dist_edge_ops import (
    dist_gated_chain_chunked, dist_get_dep_nbr_sim,
    dist_scatter_src_sim, dist_scatter_dst_sim, dist_edge_softmax_sim,
    dist_aggregate_dst_fuse_weight_sim,
)
mg1 = MirrorGraph.build(g, 1)
ch1 = chunk_edge_list(mg1, 384)
probe1 = jnp.zeros((1, ch1.dp), jnp.int32)
tables7 = (jnp.asarray(mg1.need_ids)[None][0],) + tuple(
    jnp.asarray(a) for a in (ch1.slot, ch1.dstl, ch1.dstr, ch1.mask, ch1.base)
) + (probe1,)
tables7 = tuple(
    jax.device_put(a, jax.sharding.NamedSharding(
        mesh1, jax.sharding.PartitionSpec(PARTITION_AXIS,
                                          *([None] * (a.ndim - 1)))))
    for a in tables7
)
fpay = rng.standard_normal((V, 9)).astype(np.float32)
al = rng.standard_normal((V, 1)).astype(np.float32)
ar_half = rng.standard_normal((V, 1)).astype(np.float32)
payload = np.concatenate([fpay, al], axis=1)
pay_p = jnp.asarray(mg1.pad_vertex_array(payload))
ar_p = jnp.asarray(mg1.pad_vertex_array(ar_half))
r = mg1.unpad_vertex_array(np.asarray(
    jax.jit(lambda p, a: dist_gated_chain_chunked(
        mesh1, mg1, tables7, p, a, 9, 0.2))(pay_p, ar_p),
    np.float64,
))
# golden via the UN-chunked sim chain (bit-different order, tolerance)
mir_g = dist_get_dep_nbr_sim(mg1, pay_p)
e_al = dist_scatter_src_sim(mg1, mir_g[:, :, 9:])
e_ar = dist_scatter_dst_sim(mg1, ar_p)
score_g = jax.nn.leaky_relu(e_al + e_ar, negative_slope=0.2)
s_g = dist_edge_softmax_sim(mg1, score_g)
chain_golden = mg1.unpad_vertex_array(np.asarray(
    dist_aggregate_dst_fuse_weight_sim(mg1, s_g, mir_g[:, :, :9]), np.float64
))
out["checks"]["chunked_chain_f32"] = rel_err(r, chain_golden)
out["chain_chunks"] = int(ch1.slot.shape[1])

# round 3 — eager/scatter cliff fence: lane-padded scatter parity on chip
import os as _os
_os.environ["NTS_SCATTER_LANE_PAD"] = "1"
xn = x[:, :41]  # the anomaly's narrow width
r = np.asarray(
    jax.jit(gather_dst_from_src)(dg, jnp.asarray(xn)), np.float64
)
out["checks"]["scatter_lane_pad_f32"] = rel_err(r, dense @ xn.astype(np.float64))
_os.environ.pop("NTS_SCATTER_LANE_PAD", None)

# short on-device training run: loss must decrease
from neutronstarlite_tpu.models.gcn import GCNTrainer
from neutronstarlite_tpu.graph.dataset import GNNDatum
from neutronstarlite_tpu.utils.config import InputInfo
cfg = InputInfo(); cfg.algorithm = "GCNCPU"; cfg.vertices = V
cfg.layer_string = "64-32-7"; cfg.epochs = 1; cfg.learn_rate = 0.01
cfg.weight_decay = 1e-4; cfg.decay_epoch = -1; cfg.drop_rate = 0.1
datum = GNNDatum.random_generate(V, 64, 7, seed=3)
tr = GCNTrainer.from_arrays(cfg, src, dst, datum)
import logging; logging.disable(logging.CRITICAL)
loss_first = tr.run()["loss"]          # loss after epoch 0
tr.cfg.epochs = 10                     # stateful: continues from params
loss_last = tr.run()["loss"]           # loss after 10 more epochs
out["checks"]["gcn_loss_finite"] = 0.0 if np.isfinite(loss_last) else 1.0
out["loss_first"] = loss_first
out["loss_last"] = loss_last
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def tpu_results():
    env = dict(os.environ)
    # undo the conftest's CPU pin for the child: JAX picks the TPU when the
    # machine has one, the CPU otherwise (reported back as a skip)
    env.pop("JAX_PLATFORMS", None)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in [os.path.dirname(os.path.dirname(__file__)),
                    env.get("PYTHONPATH", "")] if p
    )
    try:
        r = subprocess.run(
            [sys.executable, "-c", _TPU_SRC],
            capture_output=True, text=True, timeout=900, env=env,
        )
    except subprocess.TimeoutExpired as e:
        pytest.fail(f"on-device TPU run hung for 900 s: {(e.stderr or '')[-1500:]}")
    if r.returncode != 0 or not r.stdout.strip():
        pytest.fail(
            f"on-device TPU run failed (rc {r.returncode}): {r.stderr[-1500:]}"
        )
    info = json.loads(r.stdout.strip().splitlines()[-1])
    if "skip" in info:
        pytest.skip(info["skip"])
    return info


def test_tpu_aggregation_both_paths(tpu_results):
    checks = tpu_results["checks"]
    assert checks["agg_scatter_f32"] < 1e-5, checks
    assert checks["agg_ell_f32"] < 1e-5, checks
    # bf16 inputs: ~8-bit mantissa; accumulation error grows with degree
    assert checks["agg_scatter_bf16"] < 0.05, checks
    assert checks["agg_ell_bf16"] < 0.05, checks


def test_tpu_csr_and_gradient_pairing(tpu_results):
    checks = tpu_results["checks"]
    assert checks["agg_csr_f32"] < 1e-5, checks
    assert checks["agg_grad_f32"] < 1e-5, checks


def test_tpu_edge_softmax_chain(tpu_results):
    assert tpu_results["checks"]["edge_softmax_agg"] < 1e-4, tpu_results


def test_tpu_blocked_ell(tpu_results):
    checks = tpu_results["checks"]
    assert checks["agg_blocked_f32"] < 1e-5, checks
    assert checks["blocked_grad_f32"] < 1e-5, checks


def test_tpu_fused_gat(tpu_results):
    checks = tpu_results["checks"]
    assert checks["gat_fused_fwd"] < 1e-4, checks
    assert checks["gat_fused_grad"] < 1e-4, checks


def test_tpu_bsp_kernel(tpu_results):
    """Round 3: first Mosaic compile of the streamed block-sparse kernel
    (scalar-prefetch grid + one-hot MXU combine + output revisiting)."""
    assert tpu_results.get("bsp") == "compiled", tpu_results.get("bsp")
    assert tpu_results["checks"]["bsp_f32"] < 1e-5, tpu_results
    assert tpu_results["checks"]["bsp_grad_f32"] < 1e-5, tpu_results


def test_tpu_bsp_bf16_and_segmented(tpu_results):
    """Round 4: (a) the bf16-slab numeric policy (W entries round to the
    slab dtype for the MXU dot) stays within the bf16 tolerance class on
    chip; (b) the SMEM-budget segmented grid computes the same result."""
    assert tpu_results.get("bsp") == "compiled", tpu_results.get("bsp")
    assert "bsp_bf16_error" not in tpu_results, tpu_results["bsp_bf16_error"]
    assert tpu_results["checks"]["bsp_bf16"] < 0.05, tpu_results
    assert tpu_results.get("bsp_segments", 0) > 1, tpu_results
    assert "bsp_seg_error" not in tpu_results, tpu_results["bsp_seg_error"]
    assert tpu_results["checks"]["bsp_seg_f32"] < 1e-5, tpu_results


def test_tpu_dist_bsp_single_chip_mesh(tpu_results):
    """Round 3: the PALLAS:1 dist path (shard_map + rectangular Mosaic bsp
    + feature chunking) on real hardware over a P=1 mesh — the closest
    on-chip evidence a 1-chip rig can produce for the dist kernel."""
    assert tpu_results.get("bsp") == "compiled", tpu_results.get("bsp")
    assert tpu_results["checks"]["dist_bsp_p1_f32"] < 1e-5, tpu_results


def test_tpu_dist_bsp_segmented_on_chip(tpu_results):
    """Round 5: the SEGMENTED stacked dist-bsp layout (uniform menu
    re-lay + traced first_tile placement) executes on real hardware."""
    assert tpu_results.get("bsp") == "compiled", tpu_results.get("bsp")
    assert "dist_bsp_segmented_error" not in tpu_results, (
        tpu_results["dist_bsp_segmented_error"]
    )
    assert tpu_results.get("dist_bsp_segments", 0) > 1, tpu_results
    assert tpu_results["checks"]["dist_bsp_segmented_f32"] < 1e-5, tpu_results


def test_tpu_split_mirror_on_chip(tpu_results):
    """Round 5: the SplitMirror remote-only exchange + resident local
    edges is value-exact on chip."""
    assert tpu_results["checks"]["split_mirror_f32"] < 1e-5, tpu_results


def test_tpu_chunked_gated_chain_on_chip(tpu_results):
    """Round 5: the chunked + remat'd gated edge chain (the GAT/GGCN
    full-scale HBM fit) runs multi-chunk on chip and matches the
    un-chunked sim chain."""
    assert tpu_results.get("chain_chunks", 0) > 1, tpu_results
    assert tpu_results["checks"]["chunked_chain_f32"] < 1e-4, tpu_results


def test_tpu_scatter_lane_pad_fence(tpu_results):
    """Round 3: the eager/scatter cliff fence is value-exact on chip."""
    assert tpu_results["checks"]["scatter_lane_pad_f32"] < 1e-5, tpu_results


def test_tpu_gcn_short_training(tpu_results):
    assert tpu_results["checks"]["gcn_loss_finite"] == 0.0, tpu_results
    # training must make progress on-device: 10 further epochs after the
    # first must lower the loss
    assert tpu_results["loss_last"] < tpu_results["loss_first"], tpu_results
