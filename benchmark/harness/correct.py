"""The comparison that decides ``correct``.

The plain reference (benchmark/reference/) starts from the benchmark's own
edge list (``data.sorted_edges``) and computes its own degrees and edge
weights: nothing the program builds from the graph reaches it.

Whole-graph trainers: eval-mode logits of the program, at the weights it
holds when the window ends, against the reference at the same weights on a
seeded sample of vertices; on one chip also the gradient of the training
loss with respect to every weight, the program's by ``jax.grad`` through
its own eval forward (its aggregation's own backward pass and tables).

Sampled trainers and the server: the blocks the program's fused programs
draw for seeded seeds under a known key are taken from the program's own
``fused_sample_subgraph`` and held to the benchmark's graph (every drawn
edge is an edge of it, every live destination drew as many as its degree
and the fan-out allow, every level is the sorted distinct sources of the
level above). On them the program's logits (the trainer's eval forward;
the answers of the engine's fused bucket programs) are compared with the
reference's, which weighs the blocks itself; the trainer's gradients as
above.

Logits are compared, not classes: with weights this close to random the
largest logit turns on rounding. A logit error is the largest absolute
difference over the sample relative to the largest absolute reference
logit; a gradient error is the worst, over the weights, of the norm of
the difference relative to the norm of the reference gradient. The
tolerances are the configuration's (``tolerance`` in its file, with the
reasons), per precision: a configuration that states float32 is held to
tolerances that a bfloat16 computation does not meet.
"""

from __future__ import annotations

import importlib
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from . import data

SAMPLE_VERTICES = 4096
SAMPLE_BATCHES = 4


def reference_module(config: dict):
    return importlib.import_module(f"reference.{config['reference']}")


class ReferenceGraph:
    """The configuration's graph as the reference sees it: the benchmark's
    edge list sorted by destination (and, where a backward pass is wanted,
    by source), with the reference's own degrees and weights."""

    def __init__(self, config: dict, graph_params: dict, cache_root: str) -> None:
        self.ref = reference_module(config)
        self.graph_params, self.cache_root = graph_params, cache_root
        self.v_num = int(graph_params["vertices"])
        self.src, self.dst = data.sorted_edges(graph_params, cache_root, "dst")
        self.out_degree, self.in_degree = self.ref.degrees(self.src, self.dst, self.v_num)
        self.offsets = np.concatenate([[0], np.cumsum(self.in_degree)])

    @property
    def by_dst(self):
        w = self.ref.edge_weights(self.src, self.dst, self.out_degree, self.in_degree)
        return self.ref.Edges(take=self.src, into=self.dst, weight=w)

    @property
    def by_src(self):
        src, dst = data.sorted_edges(self.graph_params, self.cache_root, "src")
        w = self.ref.edge_weights(src, dst, self.out_degree, self.in_degree)
        return self.ref.Edges(take=dst, into=src, weight=w)

    def has_edges(self, src: np.ndarray, dst: np.ndarray) -> np.ndarray:
        """Per pair, whether src -> dst is an edge of the graph."""
        targets = np.unique(dst)
        starts = self.offsets[targets]
        lens = self.offsets[targets + 1] - starts
        at = np.repeat(starts - (np.cumsum(lens) - lens), lens) + np.arange(int(lens.sum()))
        have = np.repeat(targets, lens).astype(np.int64) * self.v_num + self.src[at]
        return np.isin(dst.astype(np.int64) * self.v_num + src, have)


def relative_error(got: np.ndarray, want: np.ndarray) -> float:
    scale = float(np.max(np.abs(want)))
    if not np.all(np.isfinite(got)) or scale == 0.0:
        return float("inf")
    return float(np.max(np.abs(got - want))) / scale


def norm_error(got: np.ndarray, want: np.ndarray) -> float:
    """||got - want|| / ||want||, Euclidean, accumulated in float64."""
    scale = float(np.linalg.norm(np.asarray(want, np.float64)))
    if not np.all(np.isfinite(got)) or scale == 0.0:
        return float("inf")
    return float(np.linalg.norm(np.asarray(got, np.float64) - want)) / scale


def gradient_error(got, want) -> float:
    """The worst ``norm_error`` over the weights (the leaves of two trees
    of one layout). By the norm and not by the largest entry: a weight's
    gradient has tens of thousands of entries, each a short sum of
    bfloat16 products, and the worst of them is an outlier of the
    rounding, not a property of the backward pass."""
    import jax

    return max(jax.tree.leaves(jax.tree.map(norm_error, got, want)))


def check_whole_graph(graph: ReferenceGraph, params: List[Dict], feature: np.ndarray,
                      label: np.ndarray, train01: np.ndarray, program_logits: np.ndarray,
                      program_grads: Optional[Any], seed: int) -> Dict[str, float]:
    """``program_grads`` None: logits only (a trainer over several chips,
    whose reference backward pass one device cannot hold)."""
    out: Dict[str, float] = {}
    if program_grads is None:
        ref = graph.ref.full_forward(graph.by_dst, params, feature)
    else:
        ref, _, ref_grads = graph.ref.full_loss_and_grads(
            graph.by_dst, graph.by_src, params, feature, label, train01
        )
        out["grad_error"] = gradient_error(program_grads, ref_grads)
    rng = np.random.default_rng(seed)
    n = min(SAMPLE_VERTICES, ref.shape[0])
    sample = rng.choice(ref.shape[0], size=n, replace=False)
    out.update(error=relative_error(program_logits[sample], ref[sample]), vertices=n)
    return out


def block_faults(graph: ReferenceGraph, nodes: Sequence[np.ndarray], hops: Sequence,
                 fanouts: Sequence[int], n_real: int, table_width: int) -> List[str]:
    """What is wrong with sampled blocks, held to the benchmark's graph;
    empty when nothing is. ``hops[h]`` is (src_local, dst_local, weight)
    from level h to level h + 1 with weight 0 on padding slots, row-major
    by destination row; level ``len(hops)`` holds the ``n_real`` seeds."""
    faults: List[str] = []
    live = int(n_real)
    for h in range(len(hops) - 1, -1, -1):
        src_local, dst_local, weight = hops[h]
        fanout = int(fanouts[h])
        valid = (np.asarray(weight) > 0).reshape(-1, fanout)
        rows = nodes[h + 1][: valid.shape[0]]
        want = np.minimum(np.minimum(graph.in_degree[rows], fanout), table_width)
        want[live:] = 0  # padding rows draw nothing
        if not np.array_equal(valid.sum(axis=1), want):
            faults.append(f"hop {h}: {int(np.sum(valid.sum(axis=1) != want))} rows drew "
                          "another number of neighbours than degree and fan-out allow")
        flat = valid.reshape(-1)
        src = nodes[h][np.asarray(src_local)[flat]]
        dst = nodes[h + 1][np.asarray(dst_local)[flat]]
        missing = int(np.sum(~graph.has_edges(src, dst)))
        if missing:
            faults.append(f"hop {h}: {missing} drawn edges are not edges of the graph")
        distinct = np.unique(src)
        if not np.array_equal(nodes[h][: len(distinct)], distinct) or np.any(nodes[h][len(distinct):]):
            faults.append(f"hop {h}: level {h} is not the sorted distinct sources")
        live = len(distinct)
    return faults


def check_blocks(graph: ReferenceGraph, params: List[Dict], feature: np.ndarray,
                 cases: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Each case: ``nodes``, ``hops`` (the program's blocks), ``caps``,
    ``fanouts``, ``n_real``, ``table_width`` and ``logits`` [n_real,
    classes] of the program on them; optionally ``grads`` of the program
    with the ``label`` and ``mask01`` of the seeds."""
    ref = graph.ref
    worst, grad_errors, faults = 0.0, [], []
    for case in cases:
        nodes, hops = case["nodes"], case["hops"]
        faults += block_faults(graph, nodes, hops, case["fanouts"], case["n_real"],
                               case["table_width"])
        own = ref.block_weights(
            nodes, [(s, d, np.asarray(w) > 0) for s, d, w in hops],
            graph.out_degree, graph.in_degree,
        )
        x0 = feature[nodes[0]]
        logits = ref.block_forward(params, x0, own, case["caps"])[: case["n_real"]]
        worst = max(worst, relative_error(case["logits"][: case["n_real"]], logits))
        if "grads" in case:
            _, ref_grads = ref.block_loss_and_grads(
                params, x0, own, case["caps"], case["label"], case["mask01"]
            )
            grad_errors.append(gradient_error(case["grads"], ref_grads))
    out: Dict[str, Any] = {"error": worst, "cases": len(cases), "block_faults": faults}
    if grad_errors:
        out["grad_error"] = max(grad_errors)
    return out


def losses_finite(losses: List[float]) -> bool:
    """Every epoch's training loss is a finite number. That it falls is
    not required: with the configurations' optimizer (Adam at 0.01 on
    batch-normalised inputs, dropout 0.5) the first steps overshoot, and
    over the nine or so epochs of a run the loss rose from 3.89 to 4.57 and
    came back to 3.91 on the chip (PERF.md, section 7). The backward pass
    is held to the reference's gradients instead."""
    return bool(len(losses) > 0 and np.all(np.isfinite(losses)))


def tolerance(config: dict, rehearse: bool) -> dict:
    """The configuration's tolerances; a rehearsal's tiny graph, on which
    roundings average out over a hundredth of the vertices, has its own."""
    tol = dict(config["tolerance"])
    if rehearse:
        tol.update(config["rehearse"].get("tolerance", {}))
    return tol


def passes(check: Dict[str, Any], tolerance: dict) -> bool:
    """The check's errors against the configuration's tolerances."""
    ok = check["error"] <= float(tolerance["logits_rel"]) and not check.get("block_faults")
    if "grad_error" in check:
        ok = ok and check["grad_error"] <= float(tolerance["grads_rel"])
    return bool(ok and check.get("losses_finite", True))
