"""Check of a vertex-classification cell over an explicit graph: full-batch,
vertex-partitioned, sampled, and served.

The plain reference (``reference/<config's reference>.py``) starts from the
benchmark's own edge list (``data.sorted_edges``) and computes its own
degrees and edge weights: nothing the program builds from the graph
reaches it.

Whole-graph trainers: eval-mode logits of the program, at the weights it
holds when the window ends (what the timed path produced), against the
reference at the same weights on a seeded sample of vertices; on one chip
also the gradient of the training loss with respect to every weight, the
program's by ``jax.grad`` through its own eval forward (its aggregation's
own backward pass and tables). Gradients are compared at the weights the
warm-up ``run()`` ended on (``record["warmup_params"]``), the same number
of trained epochs in every run: the error grows with the epochs trained
(1.2% at 7, 2.9% to 3.3% at 26 on one seed, my chip runs, PR 26), and a
faster epoch must not push an unchanged backward pass toward its limit.
The aggregate of the constant features is the same in both reference
passes and is made once.

Sampled trainers and the server: the blocks the program's fused programs
draw for seeded seeds under a known key are taken from the program's own
``fused_sample_subgraph`` and held to the benchmark's graph (every drawn
edge is an edge of it, every live destination drew as many as its degree
and the fan-out allow, every level is the sorted distinct sources of the
level above); what is wrong with them is a fault by name, as is a weight
that the window's epochs left exactly as the warm-up had it. On them the
program's logits (the trainer's eval forward; the answers of the engine's
fused bucket programs) are compared with the reference's, which weighs the
blocks itself; the trainer's gradients as above. A served answer has no
gradient.

Errors are named as the configuration's ``tolerance`` names their limits:
``logits_rel``, ``grads_rel`` (harness/correct.py has the measures).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from harness import correct, data, program

SAMPLE_VERTICES = 4096
SAMPLE_BATCHES = 4


# ---- the program, as this check reads it

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


def eval_loss_and_grads(trainer, params, loss_of_logits, targets, blocks=None):
    """(loss, gradients as float32 host arrays in the layout of the
    weights) of ``loss_of_logits(logits, *targets)`` at ``params`` over the
    program's own jitted eval forward, by ``jax.grad`` through it: the
    backward pass is the program's (its aggregation's own VJP and tables,
    its casts), without dropout and without the optimizer. ``blocks`` are
    the (nodes, hops) of a sampled trainer."""
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

    like = jax.tree.map(lambda a, b: np.asarray(a, dtype=b.dtype), params, trainer.params)
    value, grads = jax.jit(jax.value_and_grad(loss))(like, tuple(targets), *operands)
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


# ---- the reference's graph and the comparisons

class ReferenceGraph:
    """The configuration's graph as the reference sees it: the benchmark's
    edge list sorted by destination (and, where a backward pass is wanted,
    by source), with the reference's own degrees and weights."""

    def __init__(self, config: dict, graph_params: dict, cache_root: str) -> None:
        self.ref = correct.reference_module(config)
        self.graph_params, self.cache_root = graph_params, cache_root
        self.v_num = int(graph_params["vertices"])
        self.src, self.dst = data.sorted_edges(graph_params, cache_root, "dst")
        self.out_degree, self.in_degree = self.ref.degrees(self.src, self.dst, self.v_num)
        self.offsets = np.concatenate([[0], np.cumsum(self.in_degree)])

    @functools.cached_property
    def by_dst(self):
        w = self.ref.edge_weights(self.src, self.dst, self.out_degree, self.in_degree)
        return self.ref.Edges(take=self.src, into=self.dst, weight=w)

    @functools.cached_property
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


def check_whole_graph(graph: ReferenceGraph, params: List[Dict], feature: np.ndarray,
                      label: np.ndarray, train01: np.ndarray, program_logits: np.ndarray,
                      program_grads: Optional[Any], grad_params: Optional[List[Dict]],
                      seed: int) -> Dict[str, float]:
    """``program_logits`` at ``params``, ``program_grads`` at
    ``grad_params``; ``program_grads`` None: logits only (a trainer over
    several chips, whose reference backward pass one device cannot hold)."""
    errors: Dict[str, float] = {}
    ref, by_dst = graph.ref, graph.by_dst
    a0 = ref.aggregate_input(by_dst, feature)
    logits = ref.full_forward(by_dst, params, feature, a0)
    rng = np.random.default_rng(seed)
    n = min(SAMPLE_VERTICES, logits.shape[0])
    sample = rng.choice(logits.shape[0], size=n, replace=False)
    errors["logits_rel"] = correct.relative_error(program_logits[sample], logits[sample])
    if program_grads is not None:
        _, _, ref_grads = ref.full_loss_and_grads(
            by_dst, graph.by_src, grad_params, feature, label, train01, a0
        )
        errors["grads_rel"] = correct.gradient_error(program_grads, ref_grads)
    return errors


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
                 cases: List[Dict[str, Any]]) -> Tuple[Dict[str, float], List[str]]:
    """(errors, faults). Each case: ``nodes``, ``hops`` (the program's
    blocks), ``caps``, ``fanouts``, ``n_real``, ``table_width`` and
    ``logits`` [n_real, classes] of the program on them at ``params``;
    optionally ``grads`` of the program at ``grad_params`` with the
    ``label`` and ``mask01`` of the seeds."""
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
        worst = max(worst, correct.relative_error(case["logits"][: case["n_real"]], logits))
        if "grads" in case:
            _, ref_grads = ref.block_loss_and_grads(
                case["grad_params"], x0, own, case["caps"], case["label"], case["mask01"]
            )
            grad_errors.append(correct.gradient_error(case["grads"], ref_grads))
    errors = {"logits_rel": worst}
    if grad_errors:
        errors["grads_rel"] = max(grad_errors)
    return errors, faults


# ---- the cases a run is compared on

def sampled_cases(trainer, grad_params, label: np.ndarray, mask: np.ndarray, seed: int,
                  ref) -> List[Dict[str, Any]]:
    """Blocks the fused sampler draws for seeded batches of training
    vertices (the second one half full, as an epoch's last batch is), with
    the trainer's eval logits on them and, for the first, its gradients at
    ``grad_params``."""
    import jax

    batch = int(trainer.cfg.batch_size)
    train_ids = np.where(mask == 0)[0]
    rng = np.random.default_rng(seed)
    cases = []
    for i in range(SAMPLE_BATCHES):
        n_real = batch if i != 1 else batch // 2
        seeds = np.zeros(batch, dtype=np.int32)
        seeds[:n_real] = rng.choice(train_ids, size=n_real, replace=False)
        case = sampled_case(trainer, seeds, n_real, jax.random.PRNGKey(seed * 1000 + i))
        if i == 0:
            case.update(label=label[seeds], mask01=(np.arange(batch) < n_real).astype(np.float32),
                        grad_params=grad_params)
            _, case["grads"] = eval_loss_and_grads(
                trainer, grad_params, ref.masked_nll, (case["label"], case["mask01"]),
                (case["nodes"], case["hops"]),
            )
        cases.append(case)
    return cases


def served_cases(engine, mix: dict, vertices: int, seed: int) -> List[Dict[str, Any]]:
    """One request of every size of the mix, answered by the engine's fused
    bucket programs (every bucket, full and part full), with the blocks
    each drew."""
    rng = np.random.default_rng(seed + 2)
    return [
        served_case(engine, rng.integers(0, vertices, size=int(n)))
        for n in mix["seeds_per_request"]["values"]
    ]


def check(ctx, inputs, trainer, record) -> Tuple[Dict[str, float], List[str]]:
    """(errors, faults) of the run whose ``record`` this is: a served one
    where the record holds the ``engine`` that answered."""
    feature, label, mask = inputs
    graph = ReferenceGraph(ctx.config, data.graph_params(ctx.config, ctx.rehearse), ctx.cache_root)
    params = program.host_params(trainer)
    if "engine" in record:
        return check_blocks(graph, params, feature,
                            served_cases(record["engine"], ctx.traffic, graph.v_num, ctx.seed))
    family, warm = trainer_family(trainer), record["warmup_params"]
    # every weight of these models is trained: one that the window's epochs
    # left as the warm-up had it was not stepped
    faults = [f"weights {leaf} are as the warm-up left them after {record['epochs']} more epochs"
              for leaf in correct.unmoved_leaves(warm, params)]
    if family == "sampled":
        errors, block = check_blocks(graph, params, feature, sampled_cases(
            trainer, warm, label, mask, ctx.seed, graph.ref))
        return errors, faults + block
    train01 = (mask == 0).astype(np.float32)
    grads = None
    if family == "fullbatch":  # one device holds the reference's backward pass
        _, grads = eval_loss_and_grads(trainer, warm, graph.ref.masked_nll, (label, train01))
    return check_whole_graph(
        graph, params, feature, label, train01, eval_logits(trainer), grads, warm, ctx.seed,
    ), faults


# ---- the control: the reference in the program's place, a precision lower

CONTROL_DTYPE = {"bfloat16": "float8_e4m3fn", "float32": "bfloat16"}


def control(ctx) -> Dict[str, float]:
    """The errors ``check`` would return if the program were the plain
    reference computed in the nearest precision below the one the
    configuration states (fp8 for bfloat16, bfloat16 for float32): the step
    that would tempt a later PR. They have to fail the configuration's
    limits (benchmark/control.py runs this on the chip at the cell's own
    size; PERF.md section 2 has the readings). Whole-graph configurations
    only. The weights are the control's own seeded ones (Glorot, batch
    norms at one and nought): no trainer is built."""
    import jax.numpy as jnp

    from harness import spec

    config = ctx.config
    if config["cfg"]["ALGORITHM"] not in ("GCN", "GCNDIST"):
        raise spec.SpecError(f"no control for ALGORITHM {config['cfg']['ALGORITHM']}")
    graph = ReferenceGraph(config, data.graph_params(config, ctx.rehearse), ctx.cache_root)
    inputs_of = spec.config_module(config, "inputs")
    sizes = [int(w) for w in str(config["cfg"]["LAYERS"]).split("-")]
    feature, label, mask = inputs_of.make_datum(
        graph.v_num, sizes[0], sizes[-1], inputs_of.data_split(config, graph.v_num), ctx.seed
    )
    rng = np.random.default_rng(ctx.seed)
    params = []
    for i, (f_in, f_out) in enumerate(zip(sizes, sizes[1:])):
        layer = {"W": (rng.standard_normal((f_in, f_out)) * np.sqrt(2.0 / (f_in + f_out))).astype(np.float32)}
        if i < len(sizes) - 2:
            layer["bn"] = {"gamma": np.ones(f_in, np.float32), "beta": np.zeros(f_in, np.float32)}
        params.append(layer)
    dtype = getattr(jnp, CONTROL_DTYPE[str(config["cfg"].get("PRECISION", "float32"))])
    train01 = (mask == 0).astype(np.float32)
    if "grads_rel" in correct.tolerance(config, ctx.rehearse):
        logits, _, grads = graph.ref.full_loss_and_grads(
            graph.by_dst, graph.by_src, params, feature, label, train01, dtype=dtype)
    else:
        logits, grads = graph.ref.full_forward(graph.by_dst, params, feature, dtype=dtype), None
    return check_whole_graph(graph, params, feature, label, train01, logits, grads, params, ctx.seed)
