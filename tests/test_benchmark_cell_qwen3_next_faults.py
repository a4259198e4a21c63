"""The Qwen3-Next token-sequence cell's rehearsal with a fault planted in
its attention layer, its router, its shared expert or its norms (the
delta-rule layer's faults and the sound rehearsals are in
test_benchmark_cell_qwen3_next.py; two files so that ``--dist loadfile``
gives each its own worker and neither takes 300 s)."""

import pytest

from test_benchmark_cell_qwen3_next import over_limit, planted


@pytest.mark.parametrize("fault, at_least, silent", [
    ("rotary_all_dims", {"logits_rel", "grads_rel", "update_rel"}, True),
    ("output_gate_left_out", {"logits_rel", "grads_rel", "update_rel"}, True),
    ("wrong_kv_head", {"logits_rel", "grads_rel", "update_rel"}, True),
    # the choice is the same (a sigmoid keeps the order) and the reference follows it: the
    # weights' error shows in the gradients
    ("router_sigmoid", {"grads_rel"}, True),
    ("shared_gate_left_out", {"logits_rel", "grads_rel", "update_rel"}, True),
    # norm weights start at zero: with ``w`` for ``1 + w`` every layer gives nothing, and the
    # weights behind a zero never move, which the check names
    ("norm_weight_plain", {"logits_rel", "grads_rel", "update_rel"}, False),
])
def test_a_fault_planted_outside_the_delta_rule_is_not_correct(fault, at_least, silent):
    out = planted(fault)
    assert out["rc"] == 1 and out["correct"] is False
    assert at_least <= over_limit(out["compared"])
    assert out["failed"] == 0 and (out["compared"]["faults"]["value"] == 0) == silent
