"""One rehearsal of the hybrid token-sequence cell with a fault planted in
the program underneath the harness (seq_fault_driver.py's way, for the
faults a KDA layer and a latent attention without positions can have):

    python3 benchmark/tests/kda_fault_driver.py <fault> <workload>

- ``none``: nothing planted;
- ``decay_left_out``: the state is never decayed (``alpha = 1``);
- ``beta_one``: every write has full strength;
- ``state_not_carried``: every chunk starts from a zero state;
- ``qk_not_normalised``: queries and keys keep their lengths;
- ``conv_reads_later``: the convolution's window is one position late (a
  position reads the one after it);
- ``gate_sigmoid_left_out``: the output is multiplied by the gate's
  pre-activation;
- ``rotary_in_nope``: the latent attention turns its shared dims by
  position, as a DeepSeek-V3 block would.
"""

import dataclasses
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH_DIR)

import run  # noqa: E402
from harness import spec  # noqa: E402


def plant(fault: str) -> None:
    inputs = spec.named_module("inputs", "token_hybrid")
    build = inputs.build

    def faulty_build(ctx):
        import jax.numpy as jnp

        from neutronstarlite_tpu.models import seqlm
        from neutronstarlite_tpu.ops import delta_rule

        nnseq = seqlm.nnseq
        if fault == "decay_left_out":
            decay = delta_rule.chunk_log_decay
            delta_rule.chunk_log_decay = lambda g, chunk: jnp.zeros_like(decay(g, chunk))
        elif fault == "beta_one":
            rule = delta_rule.chunked_delta_rule
            delta_rule.chunked_delta_rule = (
                lambda q, k, v, log_decay, beta, cast: rule(q, k, v, log_decay, jnp.ones_like(beta), cast))
        elif fault == "state_not_carried":
            chunk = delta_rule._chunk
            delta_rule._chunk = lambda state, *rest: chunk(jnp.zeros_like(state), *rest)
        elif fault == "qk_not_normalised":
            nnseq.l2_norm = lambda x, eps=1e-6: x.astype(jnp.float32)
        elif fault == "conv_reads_later":
            conv = nnseq.causal_conv
            nnseq.causal_conv = lambda x, w: conv(jnp.roll(x, -1, axis=1), w)
        elif fault == "gate_sigmoid_left_out":
            nnseq.gated_rms_norm = (
                lambda x, w, gate, eps: nnseq.rms_norm(x, w, eps) * gate.astype(jnp.float32))
        elif fault == "rotary_in_nope":
            attention = seqlm.MIXERS["mla"]
            seqlm.MIXERS["mla"] = lambda lp, x, spec_, cast, mid: attention(
                lp, x, dataclasses.replace(spec_, rotary=True), cast, mid)
        elif fault != "none":
            raise SystemExit(f"no fault named {fault!r}")
        return build(ctx)

    inputs.build = faulty_build


if __name__ == "__main__":
    plant(sys.argv[1])
    sys.exit(run.main(["--workload", sys.argv[2], "--seed", "2886794313", "--seconds", "1",
                       "--trace", "0", "--rehearse"]))
