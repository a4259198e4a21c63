"""AOT-compile one bench (order, path) full-scale program — compiler only.

Round-3 postmortem follow-up: both full-scale pallas sweep legs timed out
on-chip, and the leading explanation is aggregate Mosaic compile time.
The chip lease and the remote COMPILER are separate services — during the
2026-07-31 lease wedge the compiler kept answering (a Cora AOT compile
took 16.5 s while every ``jax.devices()`` init hung). This tool exploits
that: it builds the EXACT program bench.py's worker would run (same
trainer factory, same synthetic Reddit graph cache, same tables) and
compiles it against a TPU topology with no chip claimed, so

1. the compile-time question ("does the merged-level pallas program
   compile, and in how long?") is answered without burning a measurement
   window, and
2. the persistent executable cache (shared dir with the workers) may be
   seeded, turning the worker's own compile into a cache hit.

``NTS_PALLAS_FORCE_COMPILED=1`` is set so the pallas executor emits real
Mosaic calls while tracing on the CPU host (interpret mode would compile
the wrong program).

Usage: python -m neutronstarlite_tpu.tools.aot_bench_path
         [--order eager] [--path pallas] [--scale 1.0]
         [--topology v5e:2x2] [--precision bfloat16]
Prints ONE JSON line: {order, path, ok, build_s, compile_s, *_gib | error}.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--order", default="eager", choices=["standard", "eager"])
    ap.add_argument(
        "--path", default="pallas",
        choices=["scatter", "ell", "blocked", "pallas", "bsp"],
    )
    ap.add_argument("--scale", type=float, default=1.0)
    ap.add_argument("--topology", default="v5e:2x2")
    ap.add_argument("--precision", default="bfloat16")
    ap.add_argument("--kernel-tile", type=int, default=8192)
    args = ap.parse_args(argv)

    # contract: no accelerator is ever claimed — host build on CPU, the
    # compile goes to the topology compiler
    import jax

    jax.config.update("jax_platforms", "cpu")
    os.environ["NTS_PALLAS_FORCE_COMPILED"] = "1"
    from neutronstarlite_tpu.utils.platform import start_runtime

    start_runtime()

    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as PS
    import numpy as np

    from bench import (
        LAYERS,
        N_LABELS,
        _make_trainer,
        build_and_cache_graph,
        load_cached_graph,
    )
    from neutronstarlite_tpu.graph.dataset import GNNDatum

    out = {"order": args.order, "path": args.path, "scale": args.scale,
           "topology": args.topology}
    t0 = time.time()
    try:
        d, v_num, _, _ = build_and_cache_graph(args.scale)
        host_graph, src, dst = load_cached_graph(d)
        sizes = [int(s) for s in LAYERS.split("-")]
        datum = GNNDatum.random_generate(v_num, sizes[0], N_LABELS, seed=7)
        trainer = _make_trainer(
            args.order, args.path, args.precision, src, dst, datum, v_num,
            epochs=1, warmup=0, host_graph=host_graph,
            kernel_tile=args.kernel_tile,
        )
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name=args.topology
        )
        mesh1 = Mesh(np.array(list(topo.devices)[:1]), ("one",))
        rep = NamedSharding(mesh1, PS())

        def spec(a):
            if hasattr(a, "shape") and hasattr(a, "dtype"):
                return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rep)
            return a

        shapes = jax.tree.map(spec, trainer.aot_args())
        out["build_s"] = round(time.time() - t0, 1)
        t0 = time.time()
        compiled = trainer._train_step.lower(*shapes).compile()
        mem = compiled.memory_analysis()
        out.update(
            ok=True,
            compile_s=round(time.time() - t0, 1),
            argument_gib=round(mem.argument_size_in_bytes / 2**30, 3),
            temp_gib=round(mem.temp_size_in_bytes / 2**30, 3),
            output_gib=round(mem.output_size_in_bytes / 2**30, 3),
        )
    except Exception as e:  # noqa: BLE001 — report, don't trace-dump
        out.update(
            ok=False, error=f"{type(e).__name__}: {str(e)[:500]}",
            elapsed_s=round(time.time() - t0, 1),
        )
    print(json.dumps(out))
    return 0 if out.get("ok") else 1


if __name__ == "__main__":
    raise SystemExit(main())
