"""The system under test, as the benchmark touches it.

Every import of ``neutronstarlite_tpu`` is in this file. The benchmark
builds a trainer through the program's own funnel (cfg file ->
``InputInfo`` -> ``from_arrays`` with a prebuilt host graph), runs its
``run()``, and serves through ``InferenceEngine`` / ``InferenceServer``.
It takes from the program its spans (the ``stages`` of ``emit_epoch``, the
marks on a ``ServeRequest``), its counters and its device arrays, and
nothing that computes a metric.
"""

from __future__ import annotations

import functools
import os
from typing import Any, Dict, List

import numpy as np

from . import data

CSC_FIELDS = (
    "column_offset", "row_indices", "dst_of_edge", "edge_weight_forward",
    "row_offset", "column_indices", "src_of_edge", "edge_weight_backward",
    "out_degree", "in_degree",
)


def configure_compile_cache() -> str:
    """The program's own placement: ``JAX_COMPILATION_CACHE_DIR`` where it
    is set, else ``<checkout>/.jax_cache``: a fixed path inside the
    checkout."""
    from neutronstarlite_tpu.utils.platform import configure_compile_cache

    return configure_compile_cache()


def host_graph(params: dict, cache_root: str, weight_mode: str = "gcn_norm"):
    """(CSCGraph, was_cached): the configuration's graph as the program's
    host structure, built by the program's ``build_graph`` from the
    benchmark's own edge list and kept on disk, since it is the same in
    every run of every cell that shares the graph."""
    from neutronstarlite_tpu.graph.storage import CSCGraph, build_graph

    v_num = int(params["vertices"])

    def build() -> Dict[str, np.ndarray]:
        src, dst = data.make_edges(params)
        g = build_graph(src, dst, v_num, weight=weight_mode)
        return {f: getattr(g, f) for f in CSC_FIELDS}

    cache_dir = os.path.join(cache_root, "graphs", f"{data.graph_key(params)}-{weight_mode}")
    arrays, cached = data.load_or_build(cache_dir, CSC_FIELDS, build)
    e_num = int(arrays["row_indices"].shape[0])
    return CSCGraph(v_num=v_num, e_num=e_num, **arrays), cached


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


def build_trainer(cfg, graph, feature, label, mask, seed: int):
    """The trainer for ``cfg.algorithm`` over the prebuilt host graph; its
    weights come from ``seed`` through the program's own initialiser."""
    from neutronstarlite_tpu.graph.dataset import GNNDatum
    from neutronstarlite_tpu.models import get_algorithm

    datum = GNNDatum(feature=feature, label=label, mask=mask)
    cls = get_algorithm(cfg.algorithm)
    return cls.from_arrays(cfg, None, None, datum, seed=seed, host_graph=graph)


def trainer_family(trainer) -> str:
    """'fullbatch', 'dist' or 'sampled': which of the program's three run
    loops this trainer has."""
    from neutronstarlite_tpu.models.fullbatch import FullBatchTrainer
    from neutronstarlite_tpu.models.gcn_dist import DistGCNTrainer
    from neutronstarlite_tpu.models.gcn_sample import GCNSampleTrainer

    for cls, name in (
        (FullBatchTrainer, "fullbatch"), (DistGCNTrainer, "dist"),
        (GCNSampleTrainer, "sampled"),
    ):
        if isinstance(trainer, cls):
            return name
    raise TypeError(f"no run-loop family known for {type(trainer).__name__}")


def host_params(trainer) -> List[Dict[str, Any]]:
    """The trainer's weights as float32 host arrays, in its own layout
    (a list of layers, each ``{"W": ...}`` and optionally ``"bn"``)."""
    import jax

    return jax.tree.map(lambda a: np.asarray(a, dtype=np.float32), trainer.params)


def eval_logits(trainer) -> np.ndarray:
    """Eval-mode logits [V, classes] of a full-batch or dist trainer at its
    current weights, through the program's own jitted eval forward."""
    import jax

    key = jax.random.PRNGKey(0)  # dropout is off in eval mode: unused
    family = trainer_family(trainer)
    if family == "fullbatch":
        return np.asarray(trainer._eval_logits(
            trainer.params, trainer.compute_graph, trainer.feature, key
        ))
    if family == "dist":
        padded = np.asarray(trainer._eval_logits(
            trainer.params, trainer.blocks, trainer.feature_p, trainer.valid_p, key
        ))
        return trainer.dist.unpad_vertex_array(padded)
    raise TypeError("a sampled trainer has no whole-graph forward")


def fused_blocks(tables, caps, fanouts, seeds_pad, n_real, key):
    """(nodes, hops): the padded multi-hop subgraph the program's fused
    programs draw for these seeds under this key, as host arrays.
    ``fused_sample_subgraph`` is the function the epoch scan and the served
    buckets trace; called here by itself, with the same tables and key, it
    gives the blocks they computed on and do not return. ``hops[h]`` is
    ``(src_local, dst_local, weight)``, the weight 0 on padding slots."""
    import jax

    nodes, hops = _fused_draw()(*tables, seeds_pad, n_real, key,
                                tuple(int(c) for c in caps), tuple(int(f) for f in fanouts))
    return jax.tree.map(np.asarray, (nodes, hops))


@functools.lru_cache(maxsize=None)
def _fused_draw():
    import jax

    from neutronstarlite_tpu.sample.fused import fused_sample_subgraph

    return jax.jit(fused_sample_subgraph, static_argnums=(7, 8))


def sampled_case(trainer, seeds_pad: np.ndarray, n_real: int, key) -> Dict[str, Any]:
    """One batch of the sampled trainer: the blocks its fused epoch scan
    draws for these seeds under this key, their shapes, and the trainer's
    eval-mode ``logits`` [B, classes] on them through its own jitted eval
    forward."""
    import jax

    runner = trainer._fused
    tables = (runner.nbr, runner.eff_deg, runner.out_deg, runner.in_deg)
    nodes, hops = fused_blocks(
        tables, runner.node_caps, runner.fanouts, seeds_pad, np.int32(n_real), key
    )
    logits = trainer._eval_batch(  # dropout is off in eval mode: the key is unused
        trainer.params, trainer.feature, list(nodes), [tuple(h) for h in hops],
        jax.random.PRNGKey(0),
    )
    return {
        "nodes": nodes, "hops": hops, "caps": runner.node_caps, "fanouts": runner.fanouts,
        "n_real": int(n_real), "table_width": int(tables[0].shape[1]),
        "logits": np.asarray(logits),
    }


def eval_loss_and_grads(trainer, loss_of_logits, targets, blocks=None):
    """(loss, gradients as float32 host arrays in the layout of the
    weights) of ``loss_of_logits(logits, *targets)`` over the program's own
    jitted eval forward, by ``jax.grad`` through it: the backward pass is
    the program's (its aggregation's own VJP and tables, its casts),
    without dropout and without the optimizer. ``blocks`` are the (nodes,
    hops) of a sampled trainer."""
    import jax

    key = jax.random.PRNGKey(0)
    family = trainer_family(trainer)
    if family == "fullbatch":
        forward, operands = trainer._eval_logits, (trainer.compute_graph, trainer.feature, key)
    elif family == "sampled":
        nodes, hops = blocks
        forward = trainer._eval_batch
        operands = (trainer.feature, list(nodes), [tuple(h) for h in hops], key)
    else:
        raise TypeError(f"no gradient comparison for a {family} trainer")

    def loss(params, targets, *ops):
        return loss_of_logits(forward(params, *ops), *targets)

    value, grads = jax.jit(jax.value_and_grad(loss))(trainer.params, tuple(targets), *operands)
    return float(value), jax.tree.map(lambda a: np.asarray(a, dtype=np.float32), grads)


def served_case(engine, ids: np.ndarray) -> Dict[str, Any]:
    """One request answered by the engine's fused program of the request's
    bucket, through the two calls the server's flush makes
    (``prepare_fused``, which draws the key, and
    ``execute_fused_prepared``): the ``logits`` [n, classes] it answered,
    the blocks that program drew under that key, and their shapes."""
    bucket = engine.sampler.bucket_for(len(ids))
    prepared = engine.prepare_fused(ids, bucket)
    logits = engine.execute_fused_prepared(prepared, bucket)[: len(ids)]
    tables, caps = engine._fused_exec_tables(), engine.sampler.node_caps(bucket)
    nodes, hops = fused_blocks(tables, caps, engine.fanouts, *prepared)
    return {
        "nodes": nodes, "hops": hops, "caps": caps, "fanouts": engine.fanouts,
        "n_real": len(ids), "table_width": int(tables[0].shape[1]),
        "logits": np.asarray(logits),
    }


def shape_facts(trainer) -> Dict[str, Any]:
    """Sizes the shape functions need and only the built trainer knows."""
    facts: Dict[str, Any] = {
        "vertices": int(trainer.host_graph.v_num),
        "edges": int(trainer.host_graph.e_num),
        "layers": [int(s) for s in trainer.cfg.layer_sizes()],
        "itemsize": 2 if trainer.cfg.precision == "bfloat16" else 4,
    }
    if trainer_family(trainer) == "dist":
        facts["partitions"] = int(trainer.dist.partitions)
        facts["vp"] = int(trainer.dist.vp)
    return facts


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
