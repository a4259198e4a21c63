"""Graph storage / config / dataset loader tests."""

import os

import numpy as np
import pytest

from neutronstarlite_tpu.graph.storage import (
    build_graph,
    load_edges_binary,
    partition_offsets,
)
from neutronstarlite_tpu.graph.dataset import GNNDatum, MASK_TRAIN, MASK_VAL, MASK_TEST
from neutronstarlite_tpu.utils.config import InputInfo

REF = "/root/reference"


def test_build_graph_csc_csr_consistency(rng):
    v = 50
    src = rng.integers(0, v, size=300, dtype=np.uint32)
    dst = rng.integers(0, v, size=300, dtype=np.uint32)
    g = build_graph(src, dst, v)

    # CSC: dst-sorted, offsets match in-degree
    assert np.all(np.diff(g.dst_of_edge) >= 0)
    assert np.all(np.diff(g.column_offset) == g.in_degree)
    # CSR: src-sorted, offsets match out-degree
    assert np.all(np.diff(g.src_of_edge) >= 0)
    assert np.all(np.diff(g.row_offset) == g.out_degree)
    # same multiset of edges in both views
    csc_edges = sorted(zip(g.row_indices.tolist(), g.dst_of_edge.tolist()))
    csr_edges = sorted(zip(g.src_of_edge.tolist(), g.column_indices.tolist()))
    assert csc_edges == csr_edges
    # same multiset of weights per (src, dst)
    assert g.edge_weight_forward.sum() == pytest.approx(
        g.edge_weight_backward.sum(), rel=1e-6
    )


def test_gcn_norm_weight_values(rng):
    # single edge 0->1 plus self loops: w(0->1) = 1/sqrt(d_out(0)*d_in(1))
    src = np.array([0, 0, 1], dtype=np.uint32)
    dst = np.array([1, 0, 1], dtype=np.uint32)
    g = build_graph(src, dst, 2)
    # d_out(0)=2, d_in(1)=2 -> 1/2
    e = [
        (s, d, w)
        for s, d, w in zip(g.row_indices, g.dst_of_edge, g.edge_weight_forward)
    ]
    w01 = [w for s, d, w in e if (s, d) == (0, 1)][0]
    assert w01 == pytest.approx(0.5)


def test_partition_offsets_balance(rng):
    v = 1000
    deg = rng.integers(1, 50, size=v).astype(np.int32)
    off = partition_offsets(v, deg, 4)
    assert off[0] == 0 and off[-1] == v
    assert np.all(np.diff(off) > 0)
    # partitions are roughly edge-balanced
    loads = [deg[off[p] : off[p + 1]].sum() for p in range(4)]
    assert max(loads) / max(min(loads), 1) < 1.5


@pytest.mark.skipif(not os.path.exists(REF), reason="reference data not mounted")
def test_load_cora_binary_edges():
    src, dst = load_edges_binary(f"{REF}/data/cora.2708.edge.self")
    assert len(src) == 13566  # 10858 + 2708 self loops
    assert src.max() < 2708 and dst.max() < 2708
    g = build_graph(src, dst, 2708)
    # every vertex has a self loop -> in_degree >= 1
    assert g.in_degree.min() >= 1


def test_load_edges_sniffing(tmp_path):
    """Format sniffing: commented text stays text; binary bytes that happen
    to start with '#' (0x23) or contain no newline stay binary."""
    from neutronstarlite_tpu.graph.storage import load_edges

    t = tmp_path / "commented.edge.txt"
    t.write_text("# generated by prep\n0 1\n1 2\n2 0\n")
    src, dst = load_edges(str(t))
    assert list(src) == [0, 1, 2] and list(dst) == [1, 2, 0]

    b = tmp_path / "hash_first.bin"
    np.array([35, 7, 9, 11, 13, 15], dtype=np.uint32).tofile(b)  # 35 == '#'
    src, dst = load_edges(str(b))
    assert list(src) == [35, 9, 13] and list(dst) == [7, 11, 15]

    b2 = tmp_path / "plain.bin"
    np.array([1000, 2000, 3000, 4000], dtype=np.uint32).tofile(b2)
    src, dst = load_edges(str(b2))
    assert list(src) == [1000, 3000] and list(dst) == [2000, 4000]


@pytest.mark.skipif(not os.path.exists(REF), reason="reference data not mounted")
def test_load_cora_labels_and_masks():
    datum = GNNDatum.read_feature_label_mask(
        feature_file="",  # cora features not shipped; random fallback
        label_file=f"{REF}/data/cora.labeltable",
        mask_file=f"{REF}/data/cora.mask",
        v_num=2708,
        feature_size=1433,
    )
    assert datum.label_num() == 7
    assert set(np.unique(datum.mask)) <= {MASK_TRAIN, MASK_VAL, MASK_TEST}
    # the shipped cora.mask split: 1605 train / 566 eval / 537 test
    assert (datum.mask == MASK_TRAIN).sum() == 1605
    assert (datum.mask == MASK_VAL).sum() == 566
    assert (datum.mask == MASK_TEST).sum() == 537


def test_cfg_parse_reference_file():
    # the repo's copy of the reference's gcn_cora.cfg (same keys and values)
    cfg = InputInfo.read_from_cfg_file(
        os.path.join(os.path.dirname(__file__), "..", "configs", "gcn_cora.cfg")
    )
    assert cfg.algorithm == "GCNCPU"
    assert cfg.vertices == 2708
    assert cfg.layer_sizes() == [1433, 128, 7]
    assert cfg.epochs == 200
    assert cfg.learn_rate == pytest.approx(0.01)
    assert cfg.weight_decay == pytest.approx(0.0001)
    assert cfg.decay_rate == pytest.approx(0.97)
    assert cfg.lock_free is True
    assert cfg.with_cuda is False
    assert cfg.drop_rate == pytest.approx(0.5)


def test_cfg_parse_fanout(tmp_path):
    p = tmp_path / "t.cfg"
    p.write_text("ALGORITHM:GCNSAMPLESINGLE\nFANOUT:5-10-10\nBATCH_SIZE:64\n")
    cfg = InputInfo.read_from_cfg_file(str(p))
    assert cfg.fanouts() == [5, 10, 10]
    assert cfg.batch_size == 64


def test_graph_digest_native_numpy_agree(rng):
    """graph_digest canonicalizes per-destination neighbor multisets, so
    the native OpenMP builder's nondeterministic tie-edge ordering (the
    PR 7 deflake root cause) cannot change the digest across builds —
    pinned by comparing the native and NumPy builders on one edge list."""
    from neutronstarlite_tpu import native
    from neutronstarlite_tpu.graph.digest import graph_digest

    v = 60
    src = rng.integers(0, v, size=400, dtype=np.uint32)
    dst = rng.integers(0, v, size=400, dtype=np.uint32)
    g_np = build_graph(src, dst, v, use_native=False)
    # permutation invariance: same multiset, shuffled edge order
    perm = rng.permutation(len(src))
    assert graph_digest(
        build_graph(src[perm], dst[perm], v, use_native=False)
    ) == graph_digest(g_np)
    # weight mode does not change the CONTENT digest (structure only)
    assert graph_digest(
        build_graph(src, dst, v, weight="ones", use_native=False)
    ) == graph_digest(g_np)
    # content sensitivity: one rerouted edge changes it
    src2 = src.copy()
    src2[0] = (src2[0] + 1) % v
    assert graph_digest(
        build_graph(src2, dst, v, use_native=False)
    ) != graph_digest(g_np)
    if native.available():
        assert graph_digest(
            build_graph(src, dst, v, use_native=True)
        ) == graph_digest(g_np)
