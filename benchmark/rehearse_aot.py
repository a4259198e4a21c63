"""Compile a cell's device program at its real shape for a described TPU,
without one.

    python3 benchmark/rehearse_aot.py --workload <name> [--topology v5e:2x2]

The third rehearsal of the on-chip-measurement guide: the host side of the
cell (graph, tables, trainer) is built on the CPU backend at the
configuration's real size, and the program the chip would run is compiled
by the installed TPU compiler for ``--topology``: what the compiler
refuses here costs no chip time. Prints one JSON line with the compile
time and ``memory_analysis()`` (bytes per device); the numbers go into the
configuration's file under ``memory``. Nothing runs on a device, so this
says nothing about results or times, and is not a chip run.

Which program is compiled: a full-batch trainer's jitted train step; a
sampled trainer's fused epoch scan; for a served cell the engine's fused
program of its largest bucket; a four-chip cell's sharded train step, as
the program's ``tools/aot_check`` builds it over a mesh of the described
devices (the trainer itself places its arrays on the devices it runs on,
which here are the CPU's).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import types

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from harness import data, spec  # noqa: E402


def memory(compiled) -> dict:
    ma = compiled.memory_analysis()
    return {
        "argument_bytes": ma.argument_size_in_bytes,
        "output_bytes": ma.output_size_in_bytes,
        "temp_bytes": ma.temp_size_in_bytes,
        "generated_code_bytes": ma.generated_code_size_in_bytes,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--topology", default="v5e:2x2")
    args = ap.parse_args(argv)

    bench = spec.load_benchmark()
    cell = spec.load_cell(bench, args.workload)
    config, chips = cell["config_data"], int(cell["chips"])
    os.environ.update({k: str(v) for k, v in config.get("env", {}).items()})
    sys.path.insert(0, spec.REPO)

    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, SingleDeviceSharding

    jax.config.update("jax_enable_compilation_cache", False)  # a described device reads none back
    from harness import program

    topo = topologies.get_topology_desc(platform="tpu", topology_name=args.topology)
    work_dir = os.path.join(spec.CACHE_DIR, "runs", "rehearse_aot")
    os.makedirs(work_dir, exist_ok=True)

    if chips > 1:
        from neutronstarlite_tpu.parallel.mesh import PARTITION_AXIS
        from neutronstarlite_tpu.tools import aot_check

        mesh = Mesh(np.array(list(topo.devices)[:chips]), (PARTITION_AXIS,))
        jitted, specs, what = aot_check._dist_gcn_case(
            program.read_cfg(config, work_dir, rehearse=False), None, mesh,
            edges=data.make_edges(data.graph_params(config, rehearse=False)),
        )
        what = f"dist train step ({what} exchange)"
    else:
        one = SingleDeviceSharding(topo.devices[0])

        def describe(tree):
            return jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(np.shape(a), jax.numpy.result_type(a), sharding=one),
                tree,
            )

        _, trainer = spec.config_module(config, "inputs").build(types.SimpleNamespace(
            config=config, spans={}, rehearse=False, cache_root=spec.CACHE_DIR,
            work_dir=work_dir, seed=1,
        ))
        family = spec.config_module(config, "check").trainer_family(trainer)
        if cell["traffic_data"]["kind"] == "open_loop":
            from neutronstarlite_tpu.serve import engine as engine_mod

            engine, server = program.build_server(trainer, work_dir, seed=1)
            server.close()
            bucket = max(engine.buckets)
            fn = engine_mod._fused_forward_fn(
                engine.sampler.node_caps(bucket), engine.fanouts, engine.compute_dtype
            )
            jitted = jax.jit(fn)
            specs = describe((
                engine.params, engine.feature, *engine._fused_exec_tables(),
                np.zeros((bucket,), np.int32), np.int32(1), jax.random.PRNGKey(0),
            ))
            what = f"serve fused bucket {bucket}"
        elif family == "sampled":
            runner = trainer._fused
            jitted = jax.jit(runner.build_epoch_fn(runner.n_batches))
            specs = describe(runner._epoch_args(
                trainer.params, trainer.opt_state, trainer.feature, trainer.label,
                0, jax.random.PRNGKey(0),
            ))
            what = f"fused epoch scan ({runner.n_batches} batches)"
        else:
            jitted, specs = trainer._train_step, describe(trainer.aot_args())
            what = "full-batch train step"

    t0 = time.time()
    compiled = jitted.lower(*specs).compile()
    print(json.dumps({
        "workload": args.workload, "topology": args.topology, "program": what,
        "compile_seconds": round(time.time() - t0, 1), "per_device": memory(compiled),
        "ran_on_a_chip": False,
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
