"""The system under test, as every cell touches it.

Imports of ``neutronstarlite_tpu`` are in this file (what any cell needs of
the program: where it keeps its compile cache, its cfg parser, a trainer's
weights and counters, the server over a trainer), in
``harness/program_spans.py`` (its flight ring), and in the modules a
configuration names: ``inputs/<name>.py`` builds its trainer through the
program's own funnel, ``checks/<name>.py`` reads its forward and backward
pass. ``kinds/``, ``needs/``, ``reference/``, ``layer_metrics/`` and the
rest of ``harness/`` import nothing of it; of the tools beside ``run.py``,
``rehearse_aot.py`` does, to compile what it names. The benchmark takes from
the program its spans (the ``stages`` of ``emit_epoch``, the marks on a
``ServeRequest``), its counters and its device arrays, and nothing that
computes a metric.
"""

from __future__ import annotations

import os
from typing import Any, Dict, List

import numpy as np


def configure_compile_cache() -> str:
    """The program's own placement: ``JAX_COMPILATION_CACHE_DIR`` where it
    is set, else ``<checkout>/.jax_cache``: a fixed path inside the
    checkout."""
    from neutronstarlite_tpu.utils.platform import configure_compile_cache

    return configure_compile_cache()


def read_cfg(config: dict, work_dir: str, rehearse: bool, extra: dict = None):
    """The configuration's KEY:VALUE settings through the program's own
    cfg parser, as a user's file would go."""
    from neutronstarlite_tpu.utils.config import InputInfo

    settings = dict(config["cfg"])
    if rehearse:
        settings.update(config["rehearse"].get("cfg", {}))
    settings.update(extra or {})
    path = os.path.join(work_dir, "cell.cfg")
    with open(path, "w") as fh:
        for key, value in settings.items():
            fh.write(f"{key}:{value}\n")
    return InputInfo.read_from_cfg_file(path)


def host_params(trainer) -> List[Dict[str, Any]]:
    """The trainer's weights (``params``, a pytree) as float32 host arrays
    in the same layout."""
    import jax

    return jax.tree.map(lambda a: np.asarray(a, dtype=np.float32), trainer.params)


def counter(trainer_or_engine, name: str) -> float:
    return float(trainer_or_engine.metrics.counter_get(name))


def build_server(trainer, work_dir: str, seed: int):
    """(engine, server) over the sampled trainer's weights: they are saved
    as the checkpoint the engine restores, the ladder is compiled ahead of
    traffic, and the in-process server is what requests are submitted to."""
    from neutronstarlite_tpu.serve.engine import InferenceEngine
    from neutronstarlite_tpu.serve.server import InferenceServer

    ckpt_dir = os.path.join(work_dir, "ckpt")
    trainer.save(ckpt_dir, 1)
    engine = InferenceEngine(
        trainer, ckpt_dir, rng=np.random.default_rng(seed),
    )
    engine.warmup()
    return engine, InferenceServer(engine)
