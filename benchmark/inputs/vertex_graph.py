"""Inputs of a vertex-classification cell over an explicit graph, and the
program's trainer over them.

The graph's topology belongs to the configuration (``graph`` in its file:
harness/data.py has the generator and the cache under benchmark/.cache/);
features, labels, the split and, through the program's own initialiser, the
weights come from ``--seed``. The trainer is built through the program's
own funnel: cfg file -> ``InputInfo`` -> ``from_arrays`` with the prebuilt
host graph.
"""

from __future__ import annotations

import os
import time
from typing import Any, Dict, List

import numpy as np

from harness import data, program, runtime

CSC_FIELDS = (
    "column_offset", "row_indices", "dst_of_edge", "edge_weight_forward",
    "row_offset", "column_indices", "src_of_edge", "edge_weight_backward",
    "out_degree", "in_degree",
)


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


def make_datum(vertices: int, feature_size: int, classes: int, split, seed: int):
    """(feature [V, f] float32, label [V] int32, mask [V] int32) from the
    seed. Labels are uniform classes; a feature row is its class's
    embedding plus unit noise, scaled by a tenth; the split (train, val,
    test sizes) is a seeded permutation."""
    if sum(split) != vertices:
        raise ValueError(f"split {split} does not sum to {vertices} vertices")
    rng = np.random.default_rng(seed)
    label = rng.integers(0, classes, size=vertices, dtype=np.int32)
    emb = rng.standard_normal((classes, feature_size), dtype=np.float32)
    feature = rng.standard_normal((vertices, feature_size), dtype=np.float32)
    feature += emb[label]
    feature *= np.float32(0.1)
    mask = np.empty(vertices, dtype=np.int32)
    order = rng.permutation(vertices)
    bounds = np.cumsum([0] + list(split))
    for which in range(3):
        mask[order[bounds[which]:bounds[which + 1]]] = which
    return feature, label, mask


def data_split(config: dict, vertices: int) -> List[int]:
    """The configuration's (train, val, test) sizes; a rehearsal's smaller
    graph keeps their proportions."""
    split = [int(s) for s in config["data"]["split"]]
    if sum(split) == vertices:
        return split
    scaled = [s * vertices // sum(split) for s in split]
    scaled[0] += vertices - sum(scaled)
    return scaled


def build(ctx):
    """((feature, label, mask), trainer): the seed's datum and the
    program's trainer for ``cfg.algorithm`` over it and the configuration's
    host graph (cached), its weights from the seed through the program's own
    initialiser; each step under a span of the benchmark's own."""
    config, spans = ctx.config, ctx.spans
    t = time.perf_counter()
    graph, cached = host_graph(data.graph_params(config, ctx.rehearse), ctx.cache_root)
    spans["graph_s"] = time.perf_counter() - t
    spans["graph_cached"] = float(cached)
    runtime.log(f"host graph V={graph.v_num} E={graph.e_num} "
                f"({'cache' if cached else 'built'}, {spans['graph_s']:.1f}s)")

    t = time.perf_counter()
    cfg = program.read_cfg(config, ctx.work_dir, ctx.rehearse)
    sizes = cfg.layer_sizes()
    feature, label, mask = make_datum(
        graph.v_num, sizes[0], sizes[-1], data_split(config, graph.v_num), ctx.seed
    )
    spans["datum_s"] = time.perf_counter() - t

    t = time.perf_counter()  # the span holds the first import of the program's models
    from neutronstarlite_tpu.graph.dataset import GNNDatum
    from neutronstarlite_tpu.models import get_algorithm

    datum = GNNDatum(feature=feature, label=label, mask=mask)
    trainer = get_algorithm(cfg.algorithm).from_arrays(
        cfg, None, None, datum, seed=ctx.seed, host_graph=graph
    )
    spans["trainer_build_s"] = time.perf_counter() - t
    runtime.log(f"trainer {type(trainer).__name__} built in {spans['trainer_build_s']:.1f}s")
    return (feature, label, mask), trainer


def shape(inputs, trainer) -> Dict[str, Any]:
    """Sizes the count (needs/) needs and only the built trainer knows."""
    facts: Dict[str, Any] = {
        "vertices": int(trainer.host_graph.v_num),
        "edges": int(trainer.host_graph.e_num),
        "layers": [int(s) for s in trainer.cfg.layer_sizes()],
        "itemsize": 2 if trainer.cfg.precision == "bfloat16" else 4,
    }
    dist = getattr(trainer, "dist", None)  # a vertex-partitioned trainer's DistGraph
    if dist is not None:
        facts["partitions"] = int(dist.partitions)
        facts["vp"] = int(dist.vp)
    return facts
