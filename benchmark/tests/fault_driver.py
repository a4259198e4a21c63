"""One rehearsal of a cell with a fault planted in the program underneath
the harness, for test_faults.py:

    python3 benchmark/tests/fault_driver.py <fault> <workload>

The harness's look for a chip is skipped (``--rehearse``); everything else
of a run is driven as ``run.py`` drives it, and the last line of stdout is
the rehearsal's. The faults, each planted where the program computes:

- ``state_unchanged``: the train step returns the weights and the
  optimizer's state it was given;
- ``half_left_out``: the aggregation leaves the sums of every second vertex
  at nought (forward and, through its transpose, backward);
- ``exchange_left_out``: the partitioned forward runs the program's own
  nn-only path (``no_exchange``), in which nothing crosses between chips.
"""

import functools
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH_DIR)

import run  # noqa: E402
from harness import spec  # noqa: E402


def plant(fault: str) -> None:
    inputs = spec.named_module("inputs", "vertex_graph")
    build = inputs.build

    def faulty_build(ctx):
        if fault == "half_left_out":
            from neutronstarlite_tpu.models import gcn

            aggregate = gcn.gather_dst_from_src
            gcn.gather_dst_from_src = lambda graph, x: aggregate(graph, x).at[::2].set(0)
        elif fault == "exchange_left_out":
            from neutronstarlite_tpu.models import gcn_dist

            gcn_dist.dist_gcn_forward = functools.partial(gcn_dist.dist_gcn_forward, no_exchange=True)
        data, trainer = build(ctx)
        if fault == "state_unchanged":
            step = trainer._train_step
            trainer._train_step = lambda params, opt, *rest: (params, opt) + tuple(step(params, opt, *rest)[2:])
        return data, trainer

    inputs.build = faulty_build


if __name__ == "__main__":
    plant(sys.argv[1])
    sys.exit(run.main(["--workload", sys.argv[2], "--seed", "2886794313", "--seconds", "1",
                       "--trace", "0", "--rehearse"]))
