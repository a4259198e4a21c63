"""Rehearsals of benchmark cells inside tier-1: ``benchmark/run.py
--rehearse`` drives a cell end to end on the CPU at its rehearsal size, as
a process of its own. The token-sequence cell (Moonlight-16B-A3B, one
chip's share) with and without a fault planted under the harness, and the
fixture cell of benchmark/tests/fixtures/token_cell, which shows that a cell
of a kind the harness has never seen enters as files and entries."""

import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
CELL = "moonlight_16b_a3b_ep8.train"
LIMIT_S = 420


def last_json_line(argv, cwd=REPO):
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    done = subprocess.run([sys.executable, *argv], cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=LIMIT_S)
    rows = [json.loads(line) for line in done.stdout.strip().splitlines() if line.startswith("{")]
    assert rows, done.stderr[-3000:]
    return dict(rows[-1], rc=done.returncode, stderr=done.stderr)


def rehearse(root, workload, trace=0):
    return last_json_line([os.path.join(root, "benchmark", "run.py"), "--workload", workload,
                           "--seed", "2886794313", "--seconds", "1", "--trace", str(trace),
                           "--rehearse"], cwd=root)


def over_limit(compared):
    return {k for k, c in compared.items() if c["value"] > c["limit"]}


@pytest.mark.parametrize("trace, reports", [
    (0, ["epoch_s", "peak_device_bytes", "setup_s"]),
    # a CPU rehearsal's trace has no device plane: the device's readers find nothing
    (1, ["compile_s", "compiles_in_window", "datum_upload_s", "first_step_backend_s",
         "first_step_s", "first_step_trace_s", "funnel_unspanned_s", "graph_build_s",
         "moe_load_max_over_mean", "runtime_start_s", "setup_cache_misses", "setup_compile_s",
         "setup_unspanned_s", "step_dispatch_ms", "step_program_mb"]),
])
def test_the_token_sequence_cell_rehearses(trace, reports):
    out = rehearse(REPO, CELL, trace)
    assert out["rc"] == 0 and out["correct"] is True, out["stderr"][-3000:]
    assert out["would_report"] == reports
    assert out["attempted"] >= 3 and out["failed"] == 0 and out["compiles_in_window"] == 0
    assert set(out["compared"]) == {"logits_rel", "route_mismatch", "loss_rel", "grads_rel",
                                    "update_rel", "faults", "losses_not_finite"}
    assert not over_limit(out["compared"])
    assert "followed step 1" in out["stderr"]


@pytest.mark.parametrize("fault, at_least", [
    ("dropped_pair", {"logits_rel", "grads_rel"}),
    ("norm_left_out", {"logits_rel", "grads_rel"}),
    ("scale_left_out", {"logits_rel", "grads_rel"}),
    ("rotary_left_out", {"grads_rel"}),
    ("shared_left_out", {"logits_rel", "grads_rel", "route_mismatch"}),
    ("non_causal_tile", {"logits_rel", "grads_rel", "loss_rel", "route_mismatch"}),
])
def test_a_fault_planted_in_the_block_is_not_correct(fault, at_least):
    out = last_json_line([os.path.join(BENCH, "tests", "seq_fault_driver.py"), fault, CELL])
    assert out["rc"] == 1 and out["correct"] is False
    assert at_least <= over_limit(out["compared"])
    assert out["failed"] == 0 and out["compared"]["faults"]["value"] == 0  # silent faults


def test_the_configuration_file_states_the_cut():
    with open(os.path.join(BENCH, "configs", "moonlight_16b_a3b_ep8.json")) as fh:
        config = json.load(fh)
    with open(os.path.join(REPO, "configs", "moonlight_16b_a3b.json")) as fh:
        published = json.load(fh)
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    for key, value in published.items():  # every width as published
        if key not in config["reduced"]:
            assert config[key] == value, key
        else:
            assert config["published"][key] == value and config[key] < value
    assert (config["num_hidden_layers"], config["n_routed_experts"], config["vocab_size"]) == (5, 8, 20480)
    limits = {k for k in config["tolerance"] if k != "reason"}
    assert limits == {"logits_rel", "route_mismatch", "loss_rel", "grads_rel", "update_rel"}
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("moonlight_16b_a3b_ep8", "train_epochs", 1)
    assert sum(1 for w in bench["workloads"] if w["chips"] == 4) == 1 and len(bench["workloads"]) == 5


@pytest.fixture(scope="module")
def token_cell_overlay(tmp_path_factory):
    """A scratch checkout: benchmark/ as it is, the program, and the
    fixture's tree laid over them (every file of it is new there)."""
    root = str(tmp_path_factory.mktemp("checkout"))
    shutil.copytree(BENCH, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    os.symlink(os.path.join(REPO, "neutronstarlite_tpu"), os.path.join(root, "neutronstarlite_tpu"))
    shutil.copytree(os.path.join(BENCH, "tests", "fixtures", "token_cell"), root, dirs_exist_ok=True)
    return root


def test_the_fixture_cell_rehearses_through_added_files_only(token_cell_overlay):
    out = rehearse(token_cell_overlay, "token_stub.train")
    assert out["rc"] == 0 and out["correct"] is True, out["stderr"][-3000:]
    assert out["would_report"] == ["epoch_s", "peak_device_bytes", "setup_s"]
    assert set(out["compared"]) == {"logits_rel", "grads_rel", "faults", "losses_not_finite"}
