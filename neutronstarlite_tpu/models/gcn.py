"""GCN toolkits: full-batch GCN and the EAGER (transform-then-propagate) variant.

Reference: toolkits/GCN_CPU.hpp / GCN.hpp — per layer a fused graph op
(ForwardCPUfuseOp / ForwardGPUfuseOp: normalized neighbor aggregation) followed
by the NN op ``dropout(relu(W * bn(n)))`` (last layer: just ``W``)
(GCN_CPU.hpp:215-228); loss is nll on masked log_softmax (:187-196); update is
gradient allreduce + hand-rolled Adam (:198-206). The EAGER variants
(GCN_CPU_EAGER.hpp:200-206) swap the order: NN first, then aggregation.

TPU design: the whole epoch is one jitted step — aggregation (chunked
segment-sum with custom_vjp, ops/aggregate.py), matmuls on the MXU, jax.grad
through the tape the reference hand-maintains (ntsContext.hpp:276-356), and
Adam fused in. Single-chip here; the distributed version is
models/gcn_dist.py via parallel/.

Layer 1's aggregate is computed once per run, not once per epoch. In the
standard order layer 0 is aggregate -> batch norm -> dense -> relu ->
dropout: the features, the tables and their gcn_norm weights are constant,
there is no input dropout and no gradient flows into the features, so
``gather_dst_from_src(graph, x)`` at ``i == 0`` is the same ``[V, f0]``
array in every epoch and in the closing eval. ``GCNTrainer`` computes it in
the funnel's ``input_aggregate`` phase with the same op over the same
tables at the same precision (``aggregate_input``), and hands it to every
step as the feature argument (``gcn_forward(..., input_aggregated=True)``).
That is exact: a loop invariant moved out of the loop. The eager order
aggregates ``nn(x)``, which is trained, and has nothing to hoist.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from neutronstarlite_tpu.models.base import register_algorithm
from neutronstarlite_tpu.models.fullbatch import FullBatchTrainer
from neutronstarlite_tpu.nn.layers import batch_norm_apply, batch_norm_init, dropout
from neutronstarlite_tpu.nn.param import xavier_uniform
from neutronstarlite_tpu.ops.aggregate import gather_dst_from_src
from neutronstarlite_tpu.utils.logging import get_logger

log = get_logger("gcn")


def init_gcn_params(key, sizes: List[int], with_bn: bool = True):
    params = []
    for i in range(len(sizes) - 1):
        key, sub = jax.random.split(key)
        layer: Dict[str, Any] = {"W": xavier_uniform(sub, sizes[i], sizes[i + 1])}
        if with_bn and i < len(sizes) - 2:
            layer["bn"] = batch_norm_init(sizes[i])
        params.append(layer)
    return params


def gcn_forward(
    graph,
    params,
    x,
    key,
    drop_rate: float,
    train: bool,
    eager: bool = False,
    compute_dtype=None,
    sublinear: bool = False,
    tap=None,
    input_aggregated: bool = False,
):
    """Logits for all vertices. ``eager`` swaps aggregate/NN order.

    ``input_aggregated``: ``x`` is already ``aggregate_input(graph,
    features)``, so layer 0 feeds it to its NN as the aggregate and runs no
    aggregation of its own (standard order only). False, every other
    caller, leaves the traced program byte-identical.

    ``tap``: optional per-layer hook ``tap(i, x) -> x`` applied to each
    layer's output (outside any jax.checkpoint rematerialization). The
    numerics plane (obs/numerics) uses it twice: the stats-fused step
    variant collects per-layer activations through it inside jit, and
    the non-finite provenance replay walks (and chaos-poisons) the layer
    chain through it eagerly. ``tap=None`` — every pre-existing caller —
    leaves the traced program byte-identical.

    ``compute_dtype=jnp.bfloat16`` runs aggregation + matmuls in bf16 (the
    TPU-native precision: halves HBM traffic for the edge-bound aggregation
    and doubles MXU throughput) while parameters and the returned logits stay
    float32 — the reference is float32-only (ValueType, dep/gemini/type.hpp:30).

    ``sublinear`` rematerializes each non-final layer in the backward pass
    instead of saving its activations — the reference's activation-
    recomputation NN op (SubLinearMemCostNNOP, core/ntsSubLinearNNOP.hpp:32),
    expressed as ``jax.checkpoint`` (SURVEY.md section 5: trade FLOPs for
    HBM). Gradients are bit-identical; only peak memory changes.
    """
    if input_aggregated and eager:
        raise ValueError(
            "the eager order aggregates nn(x), which is trained: there is "
            "no input aggregate to hand in"
        )
    if compute_dtype is not None:
        x = x.astype(compute_dtype)

    def cast(a):
        return a.astype(compute_dtype) if compute_dtype is not None else a

    n_layers = len(params)
    for i, layer in enumerate(params):
        last = i == n_layers - 1

        def nn(h, layer=layer, i=i, last=last):
            if last:
                return h @ cast(layer["W"])
            if "bn" in layer:
                h = batch_norm_apply(
                    jax.tree.map(cast, layer["bn"]), h
                )
            h = jax.nn.relu(h @ cast(layer["W"]))
            return dropout(jax.random.fold_in(key, i), h, drop_rate, train)

        def layer_step(h, nn=nn, aggregated=input_aggregated and i == 0):
            if aggregated:
                return nn(h)
            return gather_dst_from_src(graph, nn(h)) if eager else nn(
                gather_dst_from_src(graph, h)
            )

        if sublinear and not last:
            x = jax.checkpoint(layer_step)(x)
        else:
            x = layer_step(x)
        if tap is not None:
            x = tap(i, x)
    return x.astype(jnp.float32)


def aggregate_input(graph, x, compute_dtype=None):
    """Layer 1's aggregate of the standard order, ``A_hat . X``: what
    ``gcn_forward`` computes at ``i == 0`` from the features, by the same
    cast and the same op (f32 accumulation, result in ``x``'s compute
    dtype)."""
    if compute_dtype is not None:
        x = x.astype(compute_dtype)
    return gather_dst_from_src(graph, x)


@register_algorithm("GCNCPU", "GCN", "GCNTPU")
class GCNTrainer(FullBatchTrainer):
    supports_optim_kernel = True
    supports_precision = True  # gcn_forward consumes cfg.precision
    weight_mode = "gcn_norm"
    eager = False
    with_bn = True

    def init_params(self, key):
        return init_gcn_params(key, self.cfg.layer_sizes(), with_bn=self.with_bn)

    def hoists_input_aggregate(self) -> bool:
        # yes for the forward below in the standard order, and for nothing
        # that a subclass puts in its place
        cls = type(self)
        return (
            not cls.eager
            and cls.model_forward is GCNTrainer.model_forward
            and cls.forward_taped is GCNTrainer.forward_taped
        )

    @property
    def _compute_dtype(self):
        return jnp.bfloat16 if self.cfg.precision == "bfloat16" else None

    def aggregate_input(self, graph, x):
        return aggregate_input(graph, x, compute_dtype=self._compute_dtype)

    def model_forward(self, params, graph, x, key, train):
        return gcn_forward(
            graph, params, x, key,
            self.cfg.drop_rate if train else 0.0, train, eager=self.eager,
            compute_dtype=self._compute_dtype, sublinear=self.cfg.sublinear,
            input_aggregated=self.input_hoisted,
        )

    def forward_taped(self, params, graph, x, key, tap, train=True):
        """The numerics-plane hook (models/fullbatch.py): the SAME
        forward as model_forward with the per-layer tap threaded — the
        stats-fused step collects activations through it, the provenance
        replay bisects through it."""
        return gcn_forward(
            graph, params, x, key,
            self.cfg.drop_rate if train else 0.0, train, eager=self.eager,
            compute_dtype=self._compute_dtype, sublinear=self.cfg.sublinear,
            tap=tap,
            input_aggregated=self.input_hoisted,
        )


@register_algorithm("GCNCPUEAGER", "GCNEAGER", "GCNEAGERSINGLE", "GCN_CPU_EAGER")
class GCNEagerTrainer(GCNTrainer):
    """Transform-then-propagate order (GCN_CPU_EAGER.hpp:200-206)."""

    eager = True
