"""Rehearsals of the Qwen3-Next token-sequence cell (Qwen3-Next-80B-A3B, one
chip's share) inside tier-1, in a file of its own so that ``--dist
loadfile`` gives it its own worker: ``benchmark/run.py --rehearse`` drives
the cell end to end on the CPU at its rehearsal size, as a process of its
own, with and without a fault planted in a delta-rule layer under the
harness (the attention's, the router's and the norms' faults are in
test_benchmark_cell_qwen3_next_faults.py: a file stays under 300 s)."""

import json
import os

import pytest

from test_benchmark_cells import BENCH, REPO, last_json_line, over_limit, rehearse

CELL = "qwen3_next_80b_a3b_ep16.train"
COMPARED = {"logits_rel", "route_mismatch", "loss_rel", "grads_rel", "update_rel", "faults",
            "losses_not_finite"}


def planted(fault):
    return last_json_line([os.path.join(BENCH, "tests", "gdn_fault_driver.py"), fault, CELL])


@pytest.mark.parametrize("trace, reports", [
    (0, ["epoch_s", "peak_device_bytes", "setup_s"]),
    # a CPU rehearsal's trace has no device plane: the device's readers find nothing
    (1, ["compile_s", "compiles_in_window", "datum_upload_s", "first_step_backend_s",
         "first_step_s", "first_step_trace_s", "funnel_unspanned_s", "graph_build_s",
         "moe_load_max_over_mean", "runtime_start_s", "setup_cache_misses", "setup_compile_s",
         "setup_unspanned_s", "step_dispatch_ms", "step_program_mb"]),
])
def test_the_qwen3_next_cell_rehearses(trace, reports):
    out = rehearse(REPO, CELL, trace)
    assert out["rc"] == 0 and out["correct"] is True, out["stderr"][-3000:]
    assert out["would_report"] == reports
    assert out["attempted"] >= 3 and out["failed"] == 0 and out["compiles_in_window"] == 0
    assert set(out["compared"]) == COMPARED
    assert not over_limit(out["compared"])
    assert "followed step 1" in out["stderr"]


@pytest.mark.parametrize("fault", [
    "decay_left_out", "neighbour_decay", "beta_one", "state_not_carried", "wrong_key_head",
])
def test_a_fault_planted_in_a_delta_rule_layer_is_not_correct(fault):
    out = planted(fault)
    assert out["rc"] == 1 and out["correct"] is False
    assert {"logits_rel", "grads_rel", "update_rel"} <= over_limit(out["compared"])
    assert out["failed"] == 0 and out["compared"]["faults"]["value"] == 0  # silent faults


def test_the_configuration_file_states_the_cut():
    with open(os.path.join(BENCH, "configs", "qwen3_next_80b_a3b_ep16.json")) as fh:
        config = json.load(fh)
    with open(os.path.join(REPO, "configs", "qwen3_next_80b_a3b.json")) as fh:
        published = json.load(fh)
    assert config["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    for key, value in published.items():  # every width as published
        if key not in config["reduced"]:
            assert config[key] == value, key
        else:
            assert config["published"][key] == value and config[key] < value
    assert (config["num_hidden_layers"], config["num_experts"], config["vocab_size"]) == (4, 32, 18992)
    assert config["source"].endswith("Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/config.json")
    for stated in ("deployment", "assumed", "held", "batch", "memory"):
        assert config[stated], stated
    held = config["held"]["parameters"]
    assert held["total"] * 16 == held["bytes_at_16_a_parameter"]
    assert held["total"] == held["layers"] + held["embedding_head_final_norm"]
    assert held["layers"] == 3 * held["delta_rule_expert_layer"] + held["attention_expert_layer"]
    assert held["delta_rule_expert_layer"] == held["delta_rule_mixer"] + held["expert_part"]
    assert held["attention_expert_layer"] == held["attention_mixer"] + held["expert_part"]
    limits = {k for k in config["tolerance"] if k != "reason"}
    assert limits == {"logits_rel", "route_mismatch", "loss_rel", "grads_rel", "update_rel"}
    cfg = config["cfg"]
    assert (cfg["SEQ_LAYERS"], cfg["EXPERT_SHARDS"], cfg["VOCAB_SHARDS"], cfg["SEQ_LENGTH"]) == (4, 16, 8, 8192)
    assert cfg["SEQ_BATCH"] * cfg["SEQ_LENGTH"] == config["batch"]["tokens_per_step"]
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    entry = next(c for c in bench["configs"] if c["name"] == "qwen3_next_80b_a3b_ep16")
    assert entry["source"] == config["source"] and entry["reduced"] == config["reduced"]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("qwen3_next_80b_a3b_ep16", "train_epochs", 1)
    assert sum(1 for w in bench["workloads"] if w["chips"] == 4) == 1 and len(bench["workloads"]) == 5
    listed = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [])}
    assert {"gqa_attention_roofline", "kda_recurrence_roofline", "kda_layer_share", "epoch_roofline",
            "moe_experts_roofline", "moe_route_share", "graph_build_s", "compile_s",
            "compiles_in_window"} <= listed
    assert "mla_attention_roofline" not in listed  # its reader counts every layer as attending
    assert all("workloads" in m for m in bench["per_layer"])


def test_the_program_counts_the_parameters_the_file_states():
    import jax
    import numpy as np

    from neutronstarlite_tpu.models import seqlm
    from neutronstarlite_tpu.utils.config import InputInfo

    with open(os.path.join(BENCH, "configs", "qwen3_next_80b_a3b_ep16.json")) as fh:
        held = json.load(fh)["held"]["parameters"]
    with open(os.path.join(REPO, "configs", "qwen3_next_80b_a3b.json")) as fh:
        model = json.load(fh)
    cfg = InputInfo.read_from_cfg_file(os.path.join(REPO, "configs", "qwen3_next_80b_a3b_ep16.cfg"))
    spec = seqlm.SeqSpec.from_cfg(model, cfg)
    assert spec.mixers == ("kda", "kda", "kda", "gqa") and (spec.held, spec.vocab) == (32, 18992)
    assert (spec.heads, spec.kv_heads, spec.v_head, spec.rope) == (16, 2, 256, 64)
    assert (spec.kda_heads, spec.kda_value_heads, spec.kda_dim, spec.per_token) == (16, 32, 128, 10)
    shapes = jax.eval_shape(lambda key: seqlm.init_params(key, spec), jax.random.PRNGKey(0))
    count = lambda tree: int(sum(np.prod(a.shape) for a in jax.tree.leaves(tree)))  # noqa: E731
    assert count(shapes) == held["total"] == 625667136
    assert "dense" not in shapes
    assert count(shapes["moe"]) == 3 * held["delta_rule_expert_layer"]
    assert count(shapes["moe1"]) == held["attention_expert_layer"]
    experts = ("router", "eg", "eu", "ed", "sg", "su", "sd", "sgate", "norm2")
    assert count({k: shapes["moe1"][k] for k in experts}) == held["expert_part"]


def test_the_need_counts_from_the_cells_shape():
    """``attention_need`` prices K and V once a group and the attending
    layers come from the shape (not from the depth); ``recurrence_need`` is
    ``6 dk dv`` a token, value head and layer."""
    import sys

    sys.path.insert(0, BENCH)
    try:
        from harness import spec as harness_spec
        need = harness_spec.named_module("needs", "qwen3_next")
    finally:
        sys.path.remove(BENCH)
    shape = dict(length=8192, sequences=2, tokens=16384, heads=16, kv_heads=2, nope=192, rope=64,
                 v_head=256, itemsize=2, gqa_token_layers=16384.0, kda_token_layers=3 * 16384.0,
                 kda_value_heads=32, kda_key_heads=16, kda_dim=128)
    assert need.attending_layers(shape) == 1.0
    pairs = 2 * 8192 * 8193 / 2.0
    got = need.attention_need(shape)
    assert got["flops"] == 3.0 * pairs * 16 * (2 * 256 + 2 * 256)
    assert got["bytes"] == 3.0 * 16384 * (16 * 512 + 2 * 512) * 2
    rec = need.recurrence_need(shape)
    assert rec["flops"] == 3.0 * 3 * 16384 * 32 * 6.0 * 128 * 128
    assert rec["bytes"] == 3.0 * 3 * 16384 * ((2 * 32 + 2 * 16) * 128 * 2 + 32 * 8)
