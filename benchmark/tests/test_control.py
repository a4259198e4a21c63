"""The control of the whole-graph check at a size a test can hold: the
plain reference computed in fp8 (the precision below the configurations'
bfloat16) is told from the reference itself. The chip's readings at the
cells' own sizes are in PERF.md section 2; here the 2,000-vertex
rehearsal's errors are held against the limits the configuration states
for the full size, which they exceed on every seed, and against what the
program itself reads at this size."""

import json
import os

from conftest import BENCH_DIR, json_lines_of

SEEDS = "5,6,4000000007"


def test_the_reference_in_fp8_is_told_from_the_reference():
    rows, rc, stderr = json_lines_of(os.path.join(BENCH_DIR, "control.py"), "--workload",
                                     "gcn_reddit_full.train", "--seeds", SEEDS, "--rehearse")
    assert [r["seed"] for r in rows] == [int(s) for s in SEEDS.split(",")], stderr[-3000:]
    with open(os.path.join(BENCH_DIR, "configs", "gcn_reddit_full.json")) as fh:
        stated = json.load(fh)["tolerance"]
    for row in rows:
        compared = row["compared"]
        assert set(compared) == {"logits_rel", "grads_rel"}
        assert compared["logits_rel"]["value"] > stated["logits_rel"]
        assert compared["grads_rel"]["value"] > stated["grads_rel"]
        # the bfloat16 program reads 1.2% to 1.5% and 2.2% to 3.4% at this size
        assert compared["logits_rel"]["value"] > 0.025 and compared["grads_rel"]["value"] > 0.08
