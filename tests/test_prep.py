"""Dataset-prep + edge-loader format tests (generate_nts_dataset equivalent)."""

import os

import numpy as np

from neutronstarlite_tpu.graph import prep
from neutronstarlite_tpu.graph.dataset import GNNDatum
from neutronstarlite_tpu.graph.prep import prepare
from neutronstarlite_tpu.graph.storage import load_edges, load_edges_binary


def test_load_edges_sniffs_text_and_binary(tmp_path):
    src = np.array([0, 1, 2, 5], dtype=np.uint32)
    dst = np.array([1, 2, 0, 3], dtype=np.uint32)
    tpath = tmp_path / "e.edge.txt"
    with open(tpath, "w") as fh:
        for s, d in zip(src, dst):
            fh.write(f"{s} {d}\n")
    bpath = tmp_path / "e.edge.bin"
    np.stack([src, dst], axis=1).astype("<u4").tofile(bpath)
    for p in (tpath, bpath):
        s, d = load_edges(str(p))
        np.testing.assert_array_equal(s, src)
        np.testing.assert_array_equal(d, dst)


def test_prepare_cora_roundtrip(tmp_path, monkeypatch):
    # the reference's Cora files, as the repo holds them
    monkeypatch.setattr(
        prep, "REFERENCE_DATA",
        os.path.join(os.path.dirname(__file__), "fixtures", "cora"),
    )
    info = prepare("cora", str(tmp_path), text_features=True)
    assert info["v_num"] == 2708
    src, dst = load_edges_binary(info["edge_file"])
    assert len(src) == info["e_num"] == 13566
    datum = GNNDatum.read_feature_label_mask(
        info["feature_file"],
        info["label_file"],
        info["mask_file"],
        info["v_num"],
        1433,
    )
    assert datum.feature.shape == (2708, 1433)
    assert datum.label.max() == 6
    # split comes straight from the reference's cora.mask (1605/566/537)
    assert int((datum.mask == 0).sum()) == 1605
    assert int((datum.mask == 1).sum()) == 566
    assert int((datum.mask == 2).sum()) == 537


def test_prepare_synthetic_npy_features(tmp_path):
    # smallest synthetic entry; .npy feature path + real split sizes
    info = prepare("citeseer", str(tmp_path), avg_degree=3)
    assert info["feature_file"].endswith(".npy")
    datum = GNNDatum.read_feature_label_mask(
        info["feature_file"],
        info["label_file"],
        info["mask_file"],
        info["v_num"],
        3703,
    )
    assert datum.feature.shape == (3327, 3703)
    assert int((datum.mask == 0).sum()) == 120
