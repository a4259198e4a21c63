"""One rehearsal of the Qwen3-Next token-sequence cell with a fault planted
in the program underneath the harness (seq_fault_driver.py's way, for the
faults a per-head-gated delta-rule layer, a gated grouped-query attention
layer and a softmax router with a gated shared expert can have):

    python3 benchmark/tests/gdn_fault_driver.py <fault> <workload>

- ``none``: nothing planted;
- ``decay_left_out``: the state is never decayed (``alpha = 1``);
- ``neighbour_decay``: each value head takes the decay of the other value
  head of its key head;
- ``beta_one``: every write has full strength;
- ``state_not_carried``: every chunk starts from a zero state;
- ``wrong_key_head``: a value head reads the queries and keys of the key
  head before its own;
- ``rotary_all_dims``: rotary turns all of a head's dims, not its leading
  quarter;
- ``output_gate_left_out``: the attention's output is not gated;
- ``wrong_kv_head``: a query head attends the key/value head before its
  group's;
- ``router_sigmoid``: a sigmoid in the place of the router's softmax;
- ``shared_gate_left_out``: the shared expert is added without its gate;
- ``norm_weight_plain``: a norm multiplies by ``w`` in place of ``1 + w``.
"""

import dataclasses
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH_DIR)

import run  # noqa: E402
from harness import spec  # noqa: E402


def plant(fault: str) -> None:
    inputs = spec.named_module("inputs", "token_qwen3_next")
    build = inputs.build

    def faulty_build(ctx):
        import jax.numpy as jnp

        from neutronstarlite_tpu.models import seqlm
        from neutronstarlite_tpu.ops import delta_rule

        nnseq = seqlm.nnseq

        def with_spec(function, at, **changed):  # the function, told another spec
            def told(*args):
                args = list(args)
                args[at] = dataclasses.replace(args[at], **changed)
                return function(*args)
            return told

        if fault == "decay_left_out":
            decay = delta_rule.chunk_log_decay
            delta_rule.chunk_log_decay = lambda g, chunk: jnp.zeros_like(decay(g, chunk))
        elif fault == "neighbour_decay":
            decay = delta_rule.chunk_log_decay
            delta_rule.chunk_log_decay = lambda g, chunk: decay(
                g.reshape(-1, 2, *g.shape[1:])[:, ::-1].reshape(g.shape), chunk)
        elif fault == "beta_one":
            rule = delta_rule.chunked_delta_rule
            delta_rule.chunked_delta_rule = (
                lambda q, k, v, log_decay, beta, cast: rule(q, k, v, log_decay, jnp.ones_like(beta), cast))
        elif fault == "state_not_carried":
            chunk = delta_rule._chunk
            delta_rule._chunk = lambda state, *rest: chunk(jnp.zeros_like(state), *rest)
        elif fault == "wrong_key_head":
            chunk = delta_rule._chunk
            delta_rule._chunk = lambda state, q, k, *rest: chunk(
                state, jnp.roll(q, 1, axis=0), jnp.roll(k, 1, axis=0), *rest)
        elif fault == "rotary_all_dims":
            attend = seqlm.MIXERS["gqa"]
            seqlm.MIXERS["gqa"] = lambda lp, x, spec_, cast, mid: attend(
                lp, x, dataclasses.replace(spec_, rope=spec_.v_head, nope=0), cast, mid)
        elif fault == "output_gate_left_out":
            nnseq.sigmoid_gate = lambda x, gate: x.astype(jnp.float32)
        elif fault == "wrong_kv_head":
            attention = seqlm.causal_edge_attention
            seqlm.causal_edge_attention = lambda q, k, v, *rest: attention(
                q, jnp.roll(k, 1, axis=0), jnp.roll(v, 1, axis=0), *rest)
        elif fault == "router_sigmoid":
            seqlm.expert_mlp = with_spec(seqlm.expert_mlp, 3, scoring="sigmoid")
        elif fault == "shared_gate_left_out":
            seqlm.expert_mlp = with_spec(seqlm.expert_mlp, 3, shared_gate=False)
        elif fault == "norm_weight_plain":
            nnseq.centred_rms_norm = nnseq.rms_norm
        elif fault != "none":
            raise SystemExit(f"no fault named {fault!r}")
        return build(ctx)

    inputs.build = faulty_build


if __name__ == "__main__":
    plant(sys.argv[1])
    sys.exit(run.main(["--workload", sys.argv[2], "--seed", "2886794313", "--seconds", "1",
                       "--trace", "0", "--rehearse"]))
