"""One rehearsal of a token-sequence cell with a fault planted in the
program underneath the harness (fault_driver.py's way, for the faults a
DeepSeek-V3 block can have):

    python3 benchmark/tests/seq_fault_driver.py <fault> <workload>

Each is planted where the program computes, after the point at which the
program reports its choice of experts (the reference follows that choice,
so a fault in what is reported would be followed too):

- ``none``: nothing planted;
- ``dropped_pair``: the combine leaves out the first chosen expert of
  every second token;
- ``norm_left_out``: the chosen experts' weights are not normalised;
- ``scale_left_out``: the routed scaling factor is left out;
- ``rotary_left_out``: queries and keys are not rotated;
- ``shared_left_out``: the shared experts' part is not added;
- ``non_causal_tile``: a diagonal tile is not masked (a position sees the
  later positions of its block).
"""

import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH_DIR)

import run  # noqa: E402
from harness import spec  # noqa: E402


def plant(fault: str) -> None:
    inputs = spec.named_module("inputs", "token_corpus")
    build = inputs.build

    def faulty_build(ctx):
        import jax.numpy as jnp

        from neutronstarlite_tpu.models import seqlm
        from neutronstarlite_tpu.ops import causal_attention, moe

        if fault == "dropped_pair":
            combine = moe.combine_rows
            moe.combine_rows = lambda y, w, plan: combine(y, w.at[::2, 0].set(0.0), plan)
        elif fault in ("norm_left_out", "scale_left_out"):
            route = moe.route

            def faulty_route(scores, bias, per_token, scale):
                choice, weight = route(scores, bias, per_token, scale)
                if fault == "scale_left_out":
                    return choice, weight / scale
                return choice, jnp.take_along_axis(scores, choice, axis=-1) * scale

            moe.route = faulty_route
        elif fault == "rotary_left_out":
            seqlm.nnseq.rotary = lambda x, pos, theta: x.astype(jnp.float32)
        elif fault == "shared_left_out":
            expert_mlp = seqlm.expert_mlp

            def without_shared(lp, bias, x, spec_, cast):
                out, sizes, choice = expert_mlp(lp, bias, x, spec_, cast)
                hn = seqlm.nnseq.rms_norm(x, lp["norm2"], spec_.eps)
                return out - seqlm.nnseq.swiglu(hn, lp["sg"], lp["su"], lp["sd"], cast), sizes, choice

            seqlm.expert_mlp = without_shared
        elif fault == "non_causal_tile":
            def unmasked(q_i, k_j, i, j, scale, block):
                raw = causal_attention._dot("nqd,nkd->nqk", q_i, k_j) * scale
                return raw, jnp.ones((1, block, block), bool)

            causal_attention._tile_scores = unmasked
        return build(ctx)

    inputs.build = faulty_build


if __name__ == "__main__":
    plant(sys.argv[1])
    sys.exit(run.main(["--workload", sys.argv[2], "--seed", "2886794313", "--seconds", "1",
                       "--trace", "0", "--rehearse"]))
