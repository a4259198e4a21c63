"""``correct`` comes out false when the timed path is broken underneath the
harness: each fault a cell can have, planted in the program by
fault_driver.py, one rehearsal (a process of its own) a fault."""

import os

import pytest

from conftest import json_lines_of

DRIVER = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fault_driver.py")


def drive(fault: str, workload: str) -> dict:
    rows, rc, _ = json_lines_of(DRIVER, fault, workload)
    return dict(rows[-1], rc=rc)


def over_limit(compared: dict) -> set:
    return {k for k, c in compared.items() if c["value"] > c["limit"]}


@pytest.mark.parametrize("fault, workload, fails", [
    # the weights are sound where they stand, so only the faults name it
    ("state_unchanged", "gcn_reddit_full.train", {"faults"}),
    ("half_left_out", "gcn_reddit_full.train", {"logits_rel", "grads_rel"}),
    ("exchange_left_out", "gcn_products_dist4.train", {"logits_rel"}),
])
def test_a_fault_under_the_harness_is_not_correct(fault, workload, fails):
    out = drive(fault, workload)
    assert out["rc"] == 1 and out["correct"] is False
    assert over_limit(out["compared"]) == fails
    assert out["failed"] == 0  # every loss was finite: the faults are silent ones


def test_the_driver_without_a_fault_is_correct():
    out = drive("none", "gcn_reddit_full.train")
    assert out["rc"] == 0 and out["correct"] is True and not over_limit(out["compared"])
