"""The hybrid token-sequence cell's rehearsal (Kimi-Linear-48B-A3B, one
chip's share) with a fault planted in a mixer (the sound rehearsals are in
test_benchmark_cell_kimi.py; two files so that ``--dist loadfile`` gives
each its own worker)."""

import os

import pytest

from test_benchmark_cell_kimi import CELL
from test_benchmark_cells import BENCH, last_json_line, over_limit


@pytest.mark.parametrize("fault", [
    "decay_left_out", "beta_one", "state_not_carried", "qk_not_normalised", "conv_reads_later",
    "gate_sigmoid_left_out", "rotary_in_nope",
])
def test_a_fault_planted_in_a_mixer_is_not_correct(fault):
    out = last_json_line([os.path.join(BENCH, "tests", "kda_fault_driver.py"), fault, CELL])
    assert out["rc"] == 1 and out["correct"] is False
    assert over_limit(out["compared"]) & {"logits_rel", "loss_rel", "grads_rel", "update_rel"}
    assert out["failed"] == 0 and out["compared"]["faults"]["value"] == 0  # silent faults
