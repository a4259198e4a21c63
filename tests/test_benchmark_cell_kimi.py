"""Rehearsals of the hybrid token-sequence cell (Kimi-Linear-48B-A3B, one
chip's share) inside tier-1: ``benchmark/run.py --rehearse`` drives the
cell end to end on the CPU at its rehearsal size, as a process of its own.
The faults planted in its mixers are in test_benchmark_cell_kimi_faults.py:
two files so that ``--dist loadfile`` gives each its own worker."""

import json
import os

import pytest

from test_benchmark_cells import BENCH, REPO, over_limit, rehearse

CELL = "kimi_linear_48b_a3b_ep32.train"
COMPARED = {"logits_rel", "route_mismatch", "loss_rel", "grads_rel", "update_rel", "faults",
            "losses_not_finite"}


@pytest.mark.parametrize("trace, reports", [
    (0, ["epoch_s", "peak_device_bytes", "setup_s"]),
    # a CPU rehearsal's trace has no device plane: the device's readers find nothing
    (1, ["compile_s", "compiles_in_window", "datum_upload_s", "first_step_backend_s",
         "first_step_s", "first_step_trace_s", "funnel_unspanned_s", "graph_build_s",
         "moe_load_max_over_mean", "runtime_start_s", "setup_cache_misses", "setup_compile_s",
         "setup_unspanned_s", "step_dispatch_ms", "step_program_mb"]),
])
def test_the_hybrid_cell_rehearses(trace, reports):
    out = rehearse(REPO, CELL, trace)
    assert out["rc"] == 0 and out["correct"] is True, out["stderr"][-3000:]
    assert out["would_report"] == reports
    assert out["attempted"] >= 3 and out["failed"] == 0 and out["compiles_in_window"] == 0
    assert set(out["compared"]) == COMPARED
    assert not over_limit(out["compared"])
    assert "followed step 1" in out["stderr"]


def test_the_configuration_file_states_the_cut():
    with open(os.path.join(BENCH, "configs", "kimi_linear_48b_a3b_ep32.json")) as fh:
        config = json.load(fh)
    with open(os.path.join(REPO, "configs", "kimi_linear_48b_a3b.json")) as fh:
        published = json.load(fh)
    assert config["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    for key, value in published.items():  # every width as published, nested groups whole
        if key not in config["reduced"]:
            assert config[key] == value, key
        else:
            assert config["published"][key] == value and config[key] < value
    assert (config["num_hidden_layers"], config["num_experts"], config["vocab_size"]) == (5, 8, 20480)
    assert config["source"].endswith("moonshotai/Kimi-Linear-48B-A3B-Instruct/blob/main/config.json")
    for stated in ("deployment", "assumed", "held", "batch", "memory"):
        assert config[stated], stated
    held = config["held"]["parameters"]
    assert held["total"] * 16 == held["bytes_at_16_a_parameter"]
    assert held["total"] == (held["layer_1_kda_dense"] + held["expert_layers"]
                             + held["embedding_head_final_norm"])
    assert held["expert_layers"] == 3 * held["kda_expert_layer"] + held["latent_attention_expert_layer"]
    limits = {k for k in config["tolerance"] if k != "reason"}
    assert limits == {"logits_rel", "route_mismatch", "loss_rel", "grads_rel", "update_rel"}
    cfg = config["cfg"]
    assert (cfg["SEQ_LAYERS"], cfg["EXPERT_SHARDS"], cfg["VOCAB_SHARDS"], cfg["SEQ_LENGTH"]) == (5, 32, 8, 8192)
    assert cfg["SEQ_BATCH"] * cfg["SEQ_LENGTH"] == config["batch"]["tokens_per_step"]
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("kimi_linear_48b_a3b_ep32", "train_epochs", 1)
    listed = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [])}
    assert {"kda_recurrence_roofline", "kda_layer_share", "epoch_roofline", "moe_experts_roofline"} <= listed
    assert "mla_attention_roofline" not in listed  # its reader counts every layer as attending


def test_the_program_counts_the_parameters_the_file_states():
    import jax
    import numpy as np

    from neutronstarlite_tpu.models import seqlm
    from neutronstarlite_tpu.utils.config import InputInfo

    with open(os.path.join(BENCH, "configs", "kimi_linear_48b_a3b_ep32.json")) as fh:
        held = json.load(fh)["held"]["parameters"]
    with open(os.path.join(REPO, "configs", "kimi_linear_48b_a3b.json")) as fh:
        model = json.load(fh)
    cfg = InputInfo.read_from_cfg_file(os.path.join(REPO, "configs", "kimi_linear_48b_a3b_ep32.cfg"))
    spec = seqlm.SeqSpec.from_cfg(model, cfg)
    assert spec.mixers == ("kda", "kda", "kda", "mla", "kda") and (spec.held, spec.vocab) == (8, 20480)
    shapes = jax.eval_shape(lambda key: seqlm.init_params(key, spec), jax.random.PRNGKey(0))
    count = lambda tree: int(sum(np.prod(a.shape) for a in jax.tree.leaves(tree)))  # noqa: E731
    assert count(shapes) == held["total"]
    assert count(shapes["dense"]) == held["layer_1_kda_dense"]
    assert count(shapes["moe"]) == 2 * held["kda_expert_layer"]
    assert count(shapes["moe1"]) == held["latent_attention_expert_layer"]
    assert count(shapes["moe2"]) == held["kda_expert_layer"]
