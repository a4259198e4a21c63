"""The seams, driven end to end: a cell of a kind the harness has never
seen (fixtures/token_cell: token ids, a stub trainer, its own inputs, check,
count and reference) runs through ``run.py --rehearse`` with nothing but
added files, and the cells that are here run through the same seams.

Each rehearsal is a process of its own with its own time limit, started
from a scratch copy of benchmark/ with the fixture's tree laid over it.
"""

import json
import os
import shutil

import pytest

from conftest import BENCH_DIR, FIXTURES, json_lines_of

REPO = os.path.dirname(BENCH_DIR)
TOKEN_CELL = os.path.join(FIXTURES, "token_cell")


def files_under(root: str) -> set:
    return {
        os.path.relpath(os.path.join(d, f), root)
        for d, _, names in os.walk(root) for f in names
        if "__pycache__" not in d and not d.startswith(os.path.join(root, "benchmark", ".cache"))
    }


def rehearse(root: str, workload: str, trace: int = 0) -> dict:
    """The rehearsal's last line of stdout, and its exit code under ``rc``."""
    rows, rc, stderr = json_lines_of(
        os.path.join(root, "benchmark", "run.py"), "--workload", workload, "--seed", "2886794313",
        "--seconds", "1", "--trace", str(trace), "--rehearse", cwd=root)
    return dict(rows[-1], rc=rc, stderr=stderr)


@pytest.fixture(scope="module")
def overlay(tmp_path_factory):
    """A scratch checkout: benchmark/ as it is, the program, and the
    fixture's tree over them; plus two variants of its configuration as
    further entries (a fault in the stub's forward; a limit left out)."""
    root = str(tmp_path_factory.mktemp("checkout"))
    shutil.copytree(BENCH_DIR, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    os.symlink(os.path.join(REPO, "neutronstarlite_tpu"), os.path.join(root, "neutronstarlite_tpu"))
    had = files_under(root)
    added = files_under(TOKEN_CELL)
    assert not had & added, f"the fixture would replace {sorted(had & added)}"
    shutil.copytree(TOKEN_CELL, root, dirs_exist_ok=True)

    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    with open(os.path.join(root, "benchmark", "configs", "token_stub.json")) as fh:
        config = json.load(fh)
    variants = {
        "token_stub_faulty": dict(config, stub={"fault": 0.5}),
        "token_stub_no_limit": dict(config, tolerance={"logits_rel": 0.0001, "reason": "grads_rel left out"}),
    }
    for name, data in variants.items():
        path = os.path.join("benchmark", "configs", name + ".json")
        with open(os.path.join(root, path), "w") as fh:
            json.dump(dict(data, name=name), fh)
        bench["configs"].append(dict(bench["configs"][0], name=name, file=path))
        bench["workloads"].append(dict(bench["workloads"][0], name=name + ".train", config=name))
    with open(os.path.join(root, "BENCHMARK.json"), "w") as fh:
        json.dump(bench, fh)
    return root


def test_the_fixture_is_of_no_kind_that_is_here():
    with open(os.path.join(TOKEN_CELL, "benchmark", "configs", "token_stub.json")) as fh:
        config = json.load(fh)
    assert not {"graph", "cfg", "data"} & set(config)
    for key, directory in (("inputs", "inputs"), ("check", "checks"), ("need", "needs"),
                           ("reference", "reference")):
        assert os.path.isfile(os.path.join(TOKEN_CELL, "benchmark", directory, config[key] + ".py"))
        assert not os.path.exists(os.path.join(BENCH_DIR, directory, config[key] + ".py"))
    with open(os.path.join(TOKEN_CELL, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    assert [c["name"] for c in bench["configs"]] == ["token_stub"]
    assert [(w["name"], w["traffic"]) for w in bench["workloads"]] == [("token_stub.train", "train_epochs")]
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        assert "token_stub" not in fh.read()  # the repository's benchmark gains no fixture


@pytest.mark.parametrize("trace, reports", [
    (0, ["epoch_s", "peak_device_bytes", "setup_s"]),
    # a CPU rehearsal's trace has no device plane: the device's readers find nothing
    (1, ["compile_s", "compiles_in_window", "epoch_tokens", "graph_build_s"]),
])
def test_the_fixture_cell_rehearses_through_added_files_only(overlay, trace, reports):
    out = rehearse(overlay, "token_stub.train", trace)
    assert out["rc"] == 0 and out["correct"] is True, out["stderr"][-3000:]
    assert out["would_report"] == reports
    assert out["attempted"] >= 3 and out["failed"] == 0 and out["compiles_in_window"] == 0
    compared = out["compared"]
    assert set(compared) == {"logits_rel", "grads_rel", "faults", "losses_not_finite"}
    assert all(c["value"] <= c["limit"] for c in compared.values())
    # the same numbers close stderr
    assert "compared losses_not_finite: 0 against the limit 0" in out["stderr"].strip().splitlines()[-1]


def test_a_fault_in_the_stubs_forward_is_not_correct(overlay):
    out = rehearse(overlay, "token_stub_faulty.train")
    assert out["rc"] == 1 and out["correct"] is False
    compared = out["compared"]
    assert compared["logits_rel"]["value"] > 100 * compared["logits_rel"]["limit"]
    assert compared["faults"]["value"] == 0  # the weights and the inputs are sound


def test_an_error_without_a_stated_limit_is_not_correct(overlay):
    out = rehearse(overlay, "token_stub_no_limit.train")
    assert out["rc"] == 1 and out["correct"] is False
    compared = out["compared"]
    assert compared["grads_rel"]["limit"] is None
    assert compared["grads_rel"]["value"] < 0.001  # a sound value: it is the limit that is missing
    assert compared["logits_rel"]["value"] <= compared["logits_rel"]["limit"]


@pytest.mark.parametrize("workload, compares", [
    ("gcn_reddit_full.train", {"logits_rel", "grads_rel"}),
    ("gcn_products_dist4.train", {"logits_rel"}),
])
def test_the_cells_that_are_here_rehearse_through_the_same_seams(workload, compares):
    out = rehearse(REPO, workload)
    assert out["rc"] == 0 and out["correct"] is True, out["stderr"][-3000:]
    assert out["would_report"] == ["epoch_s", "peak_device_bytes", "setup_s"]
    assert set(out["compared"]) == compares | {"faults", "losses_not_finite"}
