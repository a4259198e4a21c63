"""Distributed GCN: vertex-sharded full-batch training over a device mesh.

Reference: the GCN toolkit on multiple MPI ranks (toolkits/GCN.hpp with
ForwardGPUfuseOp -> sync_compute_decoupled / compute_sync_decoupled ring
exchange, and Update()'s gradient allreduce, GCN.hpp:209-215). TPU design:

- features/labels/masks live in the padded [P*vp, .] vertex space sharded
  over the mesh axis; parameters are replicated.
- each layer's aggregation is the shard_map ppermute ring
  (parallel/dist_ops.dist_gather_dst_from_src);
- everything else (batchnorm with valid-mask statistics, matmul, relu,
  dropout, masked nll) is plain sharded array code — XLA inserts the psum
  for replicated-parameter gradients, which is exactly ``Network_simple::
  all_reduce_sum`` (comm/network.h:198) without hand-written buffers.

The whole train step is one jit; on a 1-device mesh it degenerates to the
single-chip path (ring of length 1, no collectives).

Layer 1's aggregate is computed once per run, not once per epoch. In the
standard order layer 0 is exchange -> batch norm -> dense -> relu ->
dropout over the feature slab, which is constant (no input dropout, no
gradient into the features, tables and weights built once), so
``exchange(x)`` at ``i == 0`` is the same ``[P*vp, f0]`` array in every
epoch. ``DistGCNTrainer`` computes it in the funnel's ``input_aggregate``
phase through the same ``blocks.exchange`` over its own ``blocks`` at the
same precision (``dist_aggregate_input``), sharded as ``feature_p`` is,
and every step takes it as ``feature_p`` (``dist_gcn_forward(...,
input_aggregated=True)``): exact, a loop invariant moved out of the loop.
The eager order exchanges ``nn(x)``, which is trained, and GIN / CommNet
read the raw ``x`` beside the aggregate at layer 0: neither hoists.
"""

from __future__ import annotations

import os
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as PS

from neutronstarlite_tpu.models.base import ToolkitBase, register_algorithm
from neutronstarlite_tpu.obs import skew
from neutronstarlite_tpu.resilience import elastic
from neutronstarlite_tpu.resilience.faults import fault_point
from neutronstarlite_tpu.models.gcn import init_gcn_params
from neutronstarlite_tpu.nn.layers import batch_norm_apply, compute_cast, dropout
from neutronstarlite_tpu.nn.param import AdamConfig, adam_init, adam_update
from neutronstarlite_tpu.parallel.layouts import build_exchange
from neutronstarlite_tpu.parallel.mesh import PARTITION_AXIS
from neutronstarlite_tpu.utils.logging import get_logger
from neutronstarlite_tpu.utils.timing import get_time

log = get_logger("gcn_dist")


def exchange_widths(eager: bool, sizes, input_hoisted: bool = False):
    """The per-layer EXCHANGE widths of a fuse-op dist stack, PER EPOCH:
    standard order ships each layer's INPUT width (``sizes[:-1]``); the
    eager (NN-then-exchange) variants ship the post-matmul widths
    (``sizes[1:]``). A trainer that hoists the input aggregate
    (``ToolkitBase.hoists_input_aggregate``) ships the input width
    ``sizes[0]`` once per run, in the ``input_aggregate`` phase, and
    ``sizes[1:-1]`` per epoch. ONE definition shared by the live wire
    gauges below, the tune prior (tune/runner.analytic_priors), and the
    elastic mesh reshape (resilience/elastic.replan_survivors) — three
    consumers that must never price different widths for one trainer."""
    if eager:
        return list(sizes[1:])
    return list(sizes[1:-1] if input_hoisted else sizes[:-1])


def gcn_layer_nn(i, n_layers, layer, agg, x_in, valid_mask, key, drop_rate,
                 train, compute_dtype=None, contract=None):
    """GCN's per-layer NN over the exchanged aggregate (the reference's
    vertexForward, GCN_CPU.hpp:215-228). ``compute_dtype=bf16`` runs bn +
    matmul in bf16 and RETURNS bf16, so the next layer's exchange ships
    half the bytes (the single-chip family's policy, models/gcn.py).
    ``contract`` replaces the feature-axis matmul on a 2D (vertex x
    feature) mesh (parallel/partitioner.Partitioner.contract: the
    feature-sharded contraction — XLA's all-reduce on a real mesh, the
    slab-partial sum in the sim twin); None = plain matmul, and a
    2D-padded activation meets a padded parameter only through it."""
    mm = contract or (lambda a, w: a @ w)
    cast = compute_cast(compute_dtype)
    agg = cast(agg)
    if i == n_layers - 1:
        return mm(agg, cast(layer["W"]))
    if "bn" in layer:
        agg = batch_norm_apply(jax.tree.map(cast, layer["bn"]), agg,
                               valid_mask=valid_mask)
    h = jax.nn.relu(mm(agg, cast(layer["W"])))
    return dropout(jax.random.fold_in(key, i), h, drop_rate, train)


def dist_aggregate_input(mesh, blocks, x, compute_dtype=None,
                         wire_dtype=None, partitioner=None):
    """Layer 1's aggregate of the standard order: what ``dist_gcn_forward``
    computes at ``i == 0`` from the feature slab, by the same cast and the
    same exchange."""
    return blocks.exchange(
        mesh, compute_cast(compute_dtype)(x), wire_dtype=wire_dtype,
        partitioner=partitioner,
    )


def dist_gcn_forward(
    mesh,
    blocks,
    params,
    x,
    valid_mask,
    key,
    drop_rate: float,
    train: bool,
    layer_nn=gcn_layer_nn,
    eager: bool = False,
    no_exchange: bool = False,
    compute_dtype=None,
    wire_dtype=None,
    partitioner=None,
    tap=None,
    input_aggregated: bool = False,
):
    """``blocks`` is the layout's tables and runs the exchange
    (``blocks.exchange``, parallel/layouts.py). ``layer_nn`` is
    the per-layer vertex NN over the exchanged aggregate — the fuse-op
    toolkits (GCN/GIN/CommNet)
    share the exchange engine and differ only here, exactly the reference's
    decoupled graph-op/NN-op split (ntsContext.hpp:86-95).

    ``eager`` swaps the order to NN-then-exchange (the reference's GCN_EAGER
    distributed toolkit, GCN_CPU_EAGER.hpp:200-206): every exchange — wire
    traffic AND aggregation — then runs at the post-matmul width, 602->128
    on the Reddit layer stack, the bandwidth-right order for a TPU mesh when
    d_out < d_in.

    ``tap``: optional per-layer hook ``tap(i, x) -> x`` applied to each
    layer's output — the numerics plane's seam (obs/numerics): the
    stats-fused step collects activations through it inside jit, the
    non-finite provenance replay walks and chaos-poisons the chain
    through it eagerly. ``tap=None`` (every pre-existing caller) leaves
    the traced program byte-identical.

    ``input_aggregated``: ``x`` is already ``dist_aggregate_input(...)`` of
    the feature slab, so layer 0 feeds it to ``layer_nn`` as the aggregate
    and runs no exchange of its own (standard order, and a ``layer_nn`` that
    reads its ``agg`` argument alone at layer 0). False, every other caller,
    leaves the traced program byte-identical."""
    if input_aggregated and eager:
        raise ValueError(
            "the eager order exchanges nn(x), which is trained: there is "
            "no input aggregate to hand in"
        )

    def exchange(v):
        if no_exchange:
            # DEBUGINFO's nn-only program: identical layer widths and
            # matmuls, the graph exchange replaced by identity — the
            # nn_time/graph_time split (models/debuginfo.py)
            return v
        return blocks.exchange(
            mesh, v, wire_dtype=wire_dtype, partitioner=partitioner
        )

    # PRECISION:bfloat16 — the layer_nn returns bf16 activations, so the
    # exchange (ring ppermute / all_gather / all_to_all) ships HALF the
    # bytes; every exchange's per-vertex reduction carries an explicit f32
    # accumulator (ring bodies, ELL K-reduction, split-mirror body), and
    # the logits return f32
    x = compute_cast(compute_dtype)(x)
    # 2D mesh: the feature-axis contraction (partitioner.contract — W
    # row-padding + the slab-partial sum in sim / XLA's all-reduce on a
    # real mesh) replaces the plain matmul, and each layer's activation
    # is re-pinned to the (vertex, feature) layout so the next exchange
    # starts slab-resident
    contract = partitioner.contract if partitioner is not None else None
    n_layers = len(params)
    for i, layer in enumerate(params):
        if eager:
            # transform this shard's vertices first, exchange the narrow
            # result (layer_nn's ``agg`` argument is the raw input here)
            x = exchange(
                layer_nn(i, n_layers, layer, x, x, valid_mask, key,
                         drop_rate, train, compute_dtype=compute_dtype,
                         contract=contract)
            )
        else:
            h = x if input_aggregated and i == 0 else exchange(x)
            x = layer_nn(i, n_layers, layer, h, x, valid_mask, key,
                         drop_rate, train, compute_dtype=compute_dtype,
                         contract=contract)
        if partitioner is not None and mesh is not None and i < n_layers - 1:
            x = partitioner.constrain(x)
        if tap is not None:
            x = tap(i, x)
    return x.astype(jnp.float32)


@register_algorithm("GCNDIST", "GCNTPUDIST")
class DistGCNTrainer(ToolkitBase):
    """Full-batch GCN sharded over all mesh devices (PARTITIONS cfg key)."""

    needs_device_graph = False
    weight_mode = "gcn_norm"
    with_bn = True
    supports_dist_path = True  # build_model honors DIST_PATH/WIRE_DTYPE
    supports_elastic = True  # NTS_ELASTIC=1: liveness + survivor replan
    # 2D-mesh feature padding (parallel/partitioner.pad_params_feature_dim):
    # layer 0's W and bn carry the input-feature dim; model variants
    # (GIN/CommNet) override with their own parameter names
    mesh_pad_keys = ("W", "bn")
    # per-layer NN over the exchanged aggregate; fuse-op model variants
    # (DistGINTrainer) override this and init_model_params only
    layer_nn = staticmethod(gcn_layer_nn)
    eager = False  # NN-then-exchange order (the GCN_EAGER dist toolkit)

    def init_model_params(self, key):
        return init_gcn_params(key, self.cfg.layer_sizes(), with_bn=self.with_bn)

    def hoists_input_aggregate(self) -> bool:
        # yes for GCN's own layer_nn (which reads its aggregate alone) in
        # the standard order, and for nothing a subclass puts in its place:
        # GIN and CommNet read the raw x beside the aggregate at layer 0
        cls = type(self)
        return not cls.eager and cls.layer_nn is gcn_layer_nn

    def build_model(self) -> None:
        from neutronstarlite_tpu.parallel import partitioner as pmod

        cfg = self.cfg
        self._ring_plan = None
        self._quant_probe_stats = None
        self.input_hoisted = self.hoists_input_aggregate()
        self.metrics.gauge_set("agg.input_hoisted", int(self.input_hoisted))
        # which layout this run aggregates over is decided in one place
        # (parallel/layouts.py); blocks.exchange runs it
        plan = build_exchange(
            cfg, self.host_graph, simulate=self.resolve_simulate(),
            timers=self.timers,
        )
        self.mesh, self.dist, self.blocks = plan.mesh, plan.dist, plan.blocks
        self.partitioner = plan.partitioner
        self.mesh_spec = spec = (
            plan.partitioner.spec if plan.partitioner is not None else None
        )
        self.wire_dtype = plan.wire_dtype
        self.comm_layer = layer_kind = plan.kind
        P = plan.partitions
        # elastic telemetry: the currently-planned partition count — a
        # survivor replan (resilience/elastic) rebuilds through here, so
        # the gauge tracks degradation (e.g. 4 -> 3) for free
        self.metrics.gauge_set("dist.active_partitions", P)
        if plan.table_stats is not None:
            self.record_table_stats(plan.table_stats)

        # live wire counters (obs): per-epoch forward exchange volume at
        # the actual per-layer exchange widths, priced by the SAME row
        # formula tools/wire_accounting reports offline — the run loop
        # increments these each epoch. The backward pass re-runs each
        # exchange (transposed), mirroring the forward volume; counters
        # carry the forward direction, run_summary documents the 2x.
        from neutronstarlite_tpu.tools.wire_accounting import (
            exchange_rows_per_device,
        )

        sizes = cfg.layer_sizes()
        rows = exchange_rows_per_device(
            layer_kind, P, self.dist.vp, getattr(self.dist, "mb", 0)
        )
        # a hoisting trainer ships the input width once per run (the
        # input_aggregate phase) and the hidden widths per epoch
        widths = exchange_widths(type(self).eager, sizes, self.input_hoisted)
        once = sizes[:1] if self.input_hoisted else []
        itemsize = 2 if cfg.precision == "bfloat16" else 4
        if self.wire_dtype is not None:
            # WIRE_DTYPE narrows what rides the ICI independently of the
            # compute precision — price the wire at the wire dtype
            itemsize = self.wire_dtype.itemsize
        self._wire_exchanges_per_epoch = len(widths)
        self._wire_bytes_fwd_per_epoch = rows * sum(widths) * itemsize
        wire_bytes_once = rows * sum(once) * itemsize
        self.metrics.gauge_set("wire.comm_layer", layer_kind)
        self.metrics.gauge_set("wire.rows_per_layer", rows)
        self.metrics.gauge_set(
            "wire.bytes_per_epoch_fwd", self._wire_bytes_fwd_per_epoch
        )
        if layer_kind == "ring_blocked":
            from neutronstarlite_tpu.parallel.dist_ring_blocked import (
                ring_wire_plan,
            )

            # static per-epoch ring facts -> typed per-step ring_step
            # records (run loop) + the exchange-residency gauge the smoke
            # test pins against wire_accounting. A 2D mesh prices each
            # hop at its feature-slab width (slab_width(w, Pf)) — the
            # same single definition wire_accounting.predict_mesh uses
            pf = spec.pf if spec is not None else 1
            self._ring_plan = ring_wire_plan(
                self.blocks.fwd, widths, itemsize, pf=pf
            )
            # the one-off input exchange, by the same pricing; the
            # residency gauges below are the run's peaks, so they take the
            # wider of the two
            once_plan = ring_wire_plan(self.blocks.fwd, once, itemsize, pf=pf)
            wire_bytes_once = sum(s["bytes"] for s in once_plan["steps"])
            self._ring_plan["peak_resident_feature_bytes"] = max(
                self._ring_plan["peak_resident_feature_bytes"],
                once_plan["peak_resident_feature_bytes"],
            )
            # the live counter must equal the per-hop record sum: a
            # trimmed skip SUFFIX ships fewer hops than the dense
            # (P-1)*vp formula prices (ring_schedule.trim_transfers)
            self._wire_bytes_fwd_per_epoch = sum(
                s["bytes"] for s in self._ring_plan["steps"]
            )
            self.metrics.gauge_set(
                "wire.rows_per_layer",
                self._ring_plan["transfers"] * self.dist.vp,
            )
            self.metrics.gauge_set(
                "wire.bytes_per_epoch_fwd", self._wire_bytes_fwd_per_epoch
            )
            self.metrics.gauge_set(
                "wire.peak_resident_rows",
                self._ring_plan["peak_resident_rows"],
            )
            self.metrics.gauge_set(
                "ring.skipped_steps",
                len(self._ring_plan["skipped_steps"]),
            )
            self.metrics.gauge_set(
                "ring.transfers", self._ring_plan["transfers"]
            )
            # the O(vp * f/Pf) memory claim as a live number (equals the
            # full width on the 1D mesh — Pf degenerates to 1)
            self.metrics.gauge_set(
                "wire.peak_resident_feature_bytes",
                self._ring_plan["peak_resident_feature_bytes"],
            )
            if spec is not None:
                # mesh.* gauges: the resolved 2D shape, per-axis sizes,
                # and the slab columns each rotation hop carries —
                # what OBSERVABILITY.md's mesh addendum documents and
                # the MESH_GATE pins against predict_mesh
                self.metrics.gauge_set("mesh.shape", spec.label())
                self.metrics.gauge_set("mesh.pv", spec.pv)
                self.metrics.gauge_set("mesh.pf", spec.pf)
                self.metrics.gauge_set("mesh.devices", spec.devices)
                self.metrics.gauge_set(
                    "mesh.slab_cols", self._ring_plan["slab_cols"]
                )
        elif layer_kind == "ell":
            # the all_gather family materializes every shard per device
            self.metrics.gauge_set("wire.peak_resident_rows", P * self.dist.vp)
        self.metrics.gauge_set("wire.bytes_input_aggregate", wire_bytes_once)

        # padded, sharded vertex-space data (the sim twin — mesh None —
        # keeps everything as single logical host-backed arrays, the
        # DistGCNCacheTrainer placement convention)
        pad = self.dist.pad_vertex_array
        if self.partitioner is not None and self.mesh is not None:
            # logical-axis placement (T5X rules): features live on the
            # (vertex, feature) plane — each device holds a [vp, f/Pf]
            # slab; labels/masks shard the vertex axis only; params
            # replicate
            vsh = self.partitioner.sharding("vertex", "feature")
            vsh1 = self.partitioner.sharding("vertex")
            rsh = self.partitioner.sharding()
            put = jax.device_put
        elif self.mesh is not None:
            vsh = NamedSharding(self.mesh, PS(PARTITION_AXIS, None))
            vsh1 = NamedSharding(self.mesh, PS(PARTITION_AXIS))
            rsh = NamedSharding(self.mesh, PS())
            put = jax.device_put
        else:
            vsh = vsh1 = rsh = None
            put = lambda a, s: jax.tree.map(jnp.asarray, a)  # noqa: E731
        with self.timers.phase("datum_upload"):
            # a hoisting trainer's slab is only ever read in the compute
            # dtype: it goes up in it (host_input_features)
            feat = pad(
                self.host_input_features() if self.input_hoisted
                else self.datum.feature
            )
            if self.partitioner is not None:
                # zero-pad the feature width to a Pf multiple (sim too, so
                # the twin trains the exact arrays the collective path
                # ships)
                feat = pmod.pad_feature_cols(feat, self.partitioner.pf)
            self.feature_p = put(feat, vsh)
            self.label_p = put(pad(self.datum.label.astype(np.int32)), vsh1)
            self.valid_p = put(self.dist.valid_mask(), vsh1)
            train01 = (self.datum.mask == 0).astype(np.float32)
            self.train01_p = put(pad(train01), vsh1)
            # pad fill -1 so padding rows match no mask split in the eval
            # counters
            self.mask_p = put(pad(self.datum.mask, fill=-1), vsh1)

        with self.timers.phase("params_init"):
            key = jax.random.PRNGKey(self.seed)
            params = self.init_model_params(key)
            if self.partitioner is not None:
                # zero rows meet the zero feature columns: the padded model
                # trains the unpadded math bit-for-bit on real coordinates
                params = pmod.pad_params_feature_dim(
                    params, type(self).mesh_pad_keys, sizes[0],
                    self.partitioner.pf,
                )
            self.params = put(params, rsh)
            self.adam_cfg = AdamConfig(
                alpha=cfg.learn_rate,
                weight_decay=cfg.weight_decay,
                decay_rate=cfg.decay_rate,
                decay_epoch=cfg.decay_epoch,
            )
            self.opt_state = put(adam_init(self.params), rsh)
        if self.input_hoisted:
            self._aggregate_input()
        with self.timers.phase("step_build"):
            self._build_steps()

    def _aggregate_input(self) -> None:
        """The ``input_aggregate`` phase: ``feature_p`` becomes layer 0's
        aggregate of it, computed once by the exchange the step would run
        at ``i == 0`` (same blocks, same precision), sharded as the slab
        is. Derived from the tables and the datum, so it is rebuilt with
        them here (every build_model: an elastic replan, a resume under
        another partitioning) and is not checkpointed. The raw slab is
        released before the step programs load."""
        from neutronstarlite_tpu.obs import numerics

        mesh, part = self.mesh, self.partitioner
        wire_dtype = self.wire_dtype
        compute_dtype = (
            jnp.bfloat16 if self.cfg.precision == "bfloat16" else None
        )
        raw = self.feature_p
        aggregate = jax.jit(
            lambda blocks, x: dist_aggregate_input(
                mesh, blocks, x, compute_dtype, wire_dtype, part
            ),
            **({"out_shardings": raw.sharding} if mesh is not None else {}),
        )
        rows, width = raw.shape
        with self.timers.phase(
            "input_aggregate", width=int(width), rows=int(rows),
            bytes=int(raw.nbytes),
        ):
            if wire_dtype is not None and numerics.quant_probe_enabled():
                # NTS_QUANT_PROBE reads the layer-0 payload, which rides
                # the wire here and nowhere else: measured while it lives
                from neutronstarlite_tpu.parallel.ring_schedule import (
                    payload_quant_probe,
                )

                self._quant_probe_stats = jax.device_get(
                    payload_quant_probe(wire_dtype)(raw)
                )
            self.feature_p = jax.block_until_ready(
                aggregate(self.blocks, raw)
            )
            raw.delete()

    def _build_steps(self) -> None:
        """The jit wrappers run() and the tools dispatch, and the step
        programs' cost records (build_model's ``step_build`` phase)."""
        cfg, layer_kind = self.cfg, self.comm_layer
        mesh, blocks = self.mesh, self.blocks
        P = self.dist.partitions
        drop_rate = cfg.drop_rate
        masked_nll = self.masked_nll_loss
        adam_cfg = self.adam_cfg
        layer_nn = type(self).layer_nn
        eager = type(self).eager
        # PRECISION:bfloat16 -> bf16 exchange + NN compute (f32 params,
        # wide accumulation, f32 logits)
        compute_dtype = jnp.bfloat16 if cfg.precision == "bfloat16" else None
        wire_dtype = self.wire_dtype
        part = self.partitioner
        hoisted = self.input_hoisted

        # ``blocks`` (the O(E) sharded edge arrays) is a jit ARGUMENT, not a
        # closure: captured arrays are inlined into the HLO as constants,
        # which at scale produces gigabyte programs (and remote-compile
        # paths reject them).
        @jax.jit
        def train_step(params, opt_state, blocks, feature, label, train01, valid, key):
            def loss_fn(p):
                logits = dist_gcn_forward(
                    mesh, blocks, p, feature, valid, key, drop_rate,
                    True, layer_nn, eager, compute_dtype=compute_dtype,
                    wire_dtype=wire_dtype, partitioner=part,
                    input_aggregated=hoisted,
                )
                return masked_nll(logits, label, train01), logits

            (loss, logits), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
            params, opt_state = adam_update(params, grads, opt_state, adam_cfg)
            return params, opt_state, loss, logits

        @jax.jit
        def eval_logits(params, blocks, feature, valid, key):
            return dist_gcn_forward(
                mesh, blocks, params, feature, valid, key, 0.0, False,
                layer_nn, eager, compute_dtype=compute_dtype,
                wire_dtype=wire_dtype, partitioner=part,
                input_aggregated=hoisted,
            )

        self._train_step = train_step
        self._eval_logits = eval_logits

        # numerics plane (obs/numerics, NTS_NUMERICS=1): the stats-fused
        # step variant — the default _train_step above stays untouched
        # (byte-identical program with numerics off; pinned structurally
        # in tests/test_numerics.py). Per-layer activations come through
        # dist_gcn_forward's tap seam; on a narrowed ring the layer-0
        # wire payload's stats + measured quantization error ride along.
        from neutronstarlite_tpu.obs import numerics

        self._numerics_on = numerics.numerics_enabled()
        self._train_step_stats = None
        if self._numerics_on:
            @jax.jit
            def train_step_stats(params, opt_state, blocks, feature, label,
                                 train01, valid, key):
                def loss_fn(p):
                    # taps ride the aux output (a closure list would
                    # leak grad-trace tracers out of value_and_grad)
                    acts = []

                    def tap(i, h):
                        acts.append(h)
                        return h

                    logits = dist_gcn_forward(
                        mesh, blocks, p, feature, valid, key,
                        drop_rate, True, layer_nn, eager,
                        compute_dtype=compute_dtype, wire_dtype=wire_dtype,
                        partitioner=part, tap=tap, input_aggregated=hoisted,
                    )
                    return masked_nll(logits, label, train01), (logits, acts)

                (loss, (logits, acts)), grads = jax.value_and_grad(
                    loss_fn, has_aux=True
                )(params)
                new_params, new_opt = adam_update(
                    params, grads, opt_state, adam_cfg
                )
                stats = numerics.step_stats(
                    params=new_params, grads=grads, acts=acts,
                    logits=logits,
                    # the layer-0 payload; a hoisted one rode the wire once,
                    # in the input_aggregate phase, and is not in this step
                    wire=(
                        feature if wire_dtype is not None and not hoisted
                        else None
                    ),
                    wire_dtype=wire_dtype,
                )
                return new_params, new_opt, loss, logits, stats

            self._train_step_stats = train_step_stats

        # NTS_QUANT_PROBE=1 on a narrowed ring: the per-epoch wire
        # quantization-error probe (the NTS_OVERLAP_PROBE pattern) —
        # one tiny jitted program over the layer-0 ring payload, run()
        # emits its verdict each epoch as the wire.quant_rel_err gauge
        # plus a tensor_stats record (tools/drift_audit's numerics leg
        # audits the gauge against NTS_QUANT_TOL)
        self._quant_probe_fn = None
        if self.wire_dtype is not None and numerics.quant_probe_enabled():
            from neutronstarlite_tpu.parallel.ring_schedule import (
                payload_quant_probe,
            )

            self._quant_probe_fn = payload_quant_probe(self.wire_dtype)

        # DEBUGINFO programs (models/debuginfo.py): forward loss, the same
        # forward with the exchange disabled (nn-only), and forward+grad
        def _loss(params, blocks, feature, label, train01, valid, key,
                  no_exchange=False):
            logits = dist_gcn_forward(
                mesh, blocks, params, feature, valid, key, drop_rate,
                True, layer_nn, eager, no_exchange=no_exchange,
                compute_dtype=compute_dtype, wire_dtype=wire_dtype,
                partitioner=part, input_aggregated=hoisted,
            )
            return masked_nll(logits, label, train01)

        @jax.jit
        def fwd_loss(params, blocks, feature, label, train01, valid, key):
            return _loss(params, blocks, feature, label, train01, valid, key)

        @jax.jit
        def fwd_nn_only(params, blocks, feature, label, train01, valid, key):
            return _loss(params, blocks, feature, label, train01, valid, key,
                         no_exchange=True)

        @jax.jit
        def fwd_grad(params, blocks, feature, label, train01, valid, key):
            return jax.value_and_grad(
                lambda p: _loss(p, blocks, feature, label, train01, valid, key)
            )(params)

        self._dbg_fwd = fwd_loss
        self._dbg_nn = fwd_nn_only
        self._dbg_grad = fwd_grad

        # compiled-program cost attribution (obs/cost): the whole step
        # program plus — on the ring path — the ring exchange body as its
        # own labeled program, so the exchange's FLOPs/bytes sit next to
        # the analytic wire gauges the drift auditor compares them with.
        # Both captures read the lowering only (no extra compile).
        from neutronstarlite_tpu.obs.cost import capture_program_cost

        capture_program_cost(
            self.metrics, f"dist.train_step/{type(self).__name__}",
            jitted=self._train_step, args=self.aot_args(),
        )
        if layer_kind == "ring_blocked" and (mesh is None or part is None):
            # the 1D ring body (collective or sim twin); the 2D (Pv, Pf)
            # body is already inside the captured step program — its
            # shard_map needs mesh-placed inputs a bare lowering cannot stage
            ring_fn = jax.jit(
                lambda pair, v: pair.exchange(mesh, v, wire_dtype=wire_dtype)
            )
            capture_program_cost(
                self.metrics, f"ring.body/{type(self).__name__}",
                jitted=ring_fn, args=(blocks, self.feature_p),
                partitions=int(P), simulated=mesh is None,
            )

    # ---- checkpoint canonicalization on a 2D mesh ------------------------
    # Checkpoints store the UNPADDED parameter shapes: a 2D run's mesh
    # feature padding (parallel/partitioner.pad_params_feature_dim) is
    # stripped on save and re-applied on restore, so a checkpoint written
    # under (2, 2) restores into the 1D path, a different Pf, or the
    # reshaped mesh an elastic replan emits — without this, the replan's
    # checkpoint restore would die on the pad-row shape mismatch.
    def _mesh_pad_dims(self):
        """(fin, pf) when this trainer's params carry mesh feature
        padding; None otherwise (1D, or a width that divides Pf)."""
        from neutronstarlite_tpu.parallel.partitioner import padded_width

        if self.partitioner is None:
            return None
        fin = self.cfg.layer_sizes()[0]
        pf = self.partitioner.pf
        if padded_width(fin, pf) == fin:
            return None
        return fin, pf

    def _map_param_padding(self, state, fn):
        import dataclasses as _dc

        opt = state["opt"]
        return {
            "params": fn(state["params"]),
            "opt": _dc.replace(opt, m=fn(opt.m), v=fn(opt.v)),
        }

    def checkpoint_state(self):
        state = super().checkpoint_state()
        dims = self._mesh_pad_dims()
        if dims is None:
            return state
        from neutronstarlite_tpu.parallel.partitioner import (
            unpad_params_feature_dim,
        )

        fin, pf = dims
        keys = type(self).mesh_pad_keys
        return self._map_param_padding(
            state, lambda p: unpad_params_feature_dim(p, keys, fin, pf)
        )

    def _apply_restored(self, state) -> None:
        dims = self._mesh_pad_dims()
        if dims is not None:
            from neutronstarlite_tpu.parallel.partitioner import (
                pad_params_feature_dim,
            )

            fin, pf = dims
            keys = type(self).mesh_pad_keys
            state = self._map_param_padding(
                state, lambda p: pad_params_feature_dim(p, keys, fin, pf)
            )
        super()._apply_restored(state)

    def debug_info(self, key, n: int = 3) -> str:
        """Exchange-vs-compute attribution for the dist step — the
        reference dist toolkits' DEBUGINFO report (GCN.hpp:308-353)."""
        from neutronstarlite_tpu.models.debuginfo import (
            format_dist_report,
            time_median,
        )

        args = (
            self.params, self.blocks, self.feature_p, self.label_p,
            self.train01_p, self.valid_p, key,
        )
        t_nn = time_median(self._dbg_nn, args, n)
        t_fwd = time_median(self._dbg_fwd, args, n)
        t_grad = time_median(self._dbg_grad, args, n)
        t_step = time_median(
            self._train_step,
            (self.params, self.opt_state, self.blocks, self.feature_p,
             self.label_p, self.train01_p, self.valid_p, key),
            n,
        )
        return format_dist_report(t_nn, t_fwd, t_grad, t_step)

    def aot_args(self):
        """The exact argument tuple run() passes to the jitted train step
        (tools/aot_check parity hook)."""
        return (
            self.params, self.opt_state, self.blocks, self.feature_p,
            self.label_p, self.train01_p, self.valid_p,
            jax.random.PRNGKey(self.seed + 1),
        )

    def _run_overlap_probe(self) -> None:
        """NTS_OVERLAP_PROBE=1 on a ring path: measure how much of the hop
        time the double-buffered schedule hides under the blocked compute
        (parallel/dist_ring_blocked.measure_overlap over the first-layer
        exchange), then pin the verdict as gauges + one probe span so
        tools/trace_timeline and metrics_report report a MEASURED overlap
        efficiency instead of an asserted one. Costs three small compiles;
        off by default."""
        from neutronstarlite_tpu.parallel.dist_ring_blocked import (
            measure_overlap,
        )

        from neutronstarlite_tpu.parallel.mesh import (
            FEATURE_AXIS,
            PARTITION_AXIS,
            VERTEX_AXIS,
        )

        axes = (
            (VERTEX_AXIS, FEATURE_AXIS)
            if self.partitioner is not None
            else (PARTITION_AXIS, None)
        )
        h = self.tracer.begin("ring_overlap_probe", cat="probe")
        try:
            probe = measure_overlap(
                self.blocks.fwd, self.feature_p, mesh=self.mesh,
                wire_dtype=self.wire_dtype, axes=axes,
            )
        except BaseException as e:
            # run() swallows probe failures; the span must still emit (and
            # pop off the stack) or later spans parent under a ghost
            self.tracer.end(h, error=type(e).__name__)
            raise
        self.tracer.end(h, **probe)
        if probe["efficiency"] is not None:
            self.metrics.gauge_set(
                "ring.overlap_efficiency", probe["efficiency"]
            )
        self.metrics.gauge_set("ring.probe_overlap_s", probe["overlap_s"])
        self.metrics.gauge_set("ring.probe_compute_s", probe["compute_s"])
        self.metrics.gauge_set("ring.probe_exchange_s", probe["exchange_s"])
        self.metrics.gauge_set(
            "ring.probe_simulated", bool(probe["simulated"])
        )
        log.info(
            "ring overlap probe%s: overlapped %.3f ms, compute-only %.3f "
            "ms, exchange-only %.3f ms -> efficiency %s",
            " (sim)" if probe["simulated"] else "",
            probe["overlap_s"] * 1e3, probe["compute_s"] * 1e3,
            probe["exchange_s"] * 1e3,
            f"{probe['efficiency']:.2f}" if probe["efficiency"] is not None
            else "n/a",
        )

    def _emit_quant_probe(self, epoch: int) -> None:
        """One NTS_QUANT_PROBE verdict per epoch: the measured relative
        RMS error of the layer-0 ring payload at the wire dtype vs its
        f32 master, as wire.quant_rel_err + a tensor_stats record. The
        layer-0 payload (the feature slab) is STATIC across epochs, so
        the device measurement runs once and the per-epoch cadence
        re-emits the cached verdict — a Reddit-scale feature matrix must
        not pay a full cast+reduce+fetch per epoch to recompute a
        constant. Best-effort (a probe must never kill the run)."""
        from neutronstarlite_tpu.obs import numerics

        try:
            # a hoisting trainer measured it in the input_aggregate phase,
            # where the payload lived
            stats = self._quant_probe_stats
            if stats is None:
                stats = jax.device_get(self._quant_probe_fn(self.feature_p))
                self._quant_probe_stats = stats
            numerics.emit_payload_stats(
                self.metrics, stats, epoch, name="wire.payload/l0"
            )
        except Exception as e:
            log.warning("wire quant probe failed at epoch %d: %s", epoch, e)

    def numerics_replay(self, epoch: int):
        """The non-finite provenance replay (obs/numerics): the failing
        epoch's forward re-run EAGERLY through dist_gcn_forward's tap
        seam — same inputs, same fold_in key, chaos poison applied
        mid-layer (``poison_hook``)."""
        from neutronstarlite_tpu.obs import numerics

        key = jax.random.fold_in(jax.random.PRNGKey(self.seed + 1), epoch)
        entries = []

        def tap(i, h):
            h = numerics.poison_hook(h, i)
            entries.append((i, "activation", f"acts/l{i}", h))
            return h

        compute_dtype = (
            jnp.bfloat16 if self.cfg.precision == "bfloat16" else None
        )
        logits = dist_gcn_forward(
            self.mesh, self.blocks, self.params, self.feature_p,
            self.valid_p, key, self.cfg.drop_rate, True,
            type(self).layer_nn, type(self).eager,
            compute_dtype=compute_dtype, wire_dtype=self.wire_dtype,
            partitioner=self.partitioner, tap=tap,
            input_aggregated=self.input_hoisted,
        )
        entries.append((None, "logits", "logits", logits))
        return entries

    def _emit_dist_epoch(self, epoch: int, dt: float, loss,
                         dispatch_s: float, device_s: float) -> None:
        """The ``epoch_emit`` stage of the dist loop: the epoch event with
        its wire counters and guards, then the ring-step, straggler and
        liveness records. Runs after the epoch's loss is in the history
        and BEFORE ckpt_epoch_end."""
        self.record_epoch_wire(
            epoch, dt, loss, self._wire_bytes_fwd_per_epoch,
            self._wire_exchanges_per_epoch,
            stages={"step_dispatch": dispatch_s, "step_device": device_s},
        )
        if self._ring_plan is not None:
            # typed per-rotation-hop records: bytes shipped per device
            # this epoch (all layer exchanges, forward direction) and
            # the static skip verdict. Per-hop wall time is not
            # separable inside one XLA program — ``seconds`` is null
            # here; parallel/comm_bench.py measures it standalone and
            # the NTS_OVERLAP_PROBE run attributes hidden-vs-exposed
            # hop time. ``epoch_span`` joins each hop to its epoch's
            # span on the causal timeline.
            espan = self._last_epoch_span
            for hop in self._ring_plan["steps"]:
                self.metrics.event(
                    "ring_step", epoch=epoch, step=hop["step"],
                    bytes=int(hop["bytes"]), skipped=hop["skipped"],
                    seconds=None,
                    slab_cols=int(hop["slab_cols"]),
                    epoch_span=espan.span_id if espan else None,
                )
        part_seconds = None
        if self._liveness is not None or self._straggler is not None:
            # per-partition step attribution: the sim twin executes
            # every partition inside ONE fused XLA step, so each
            # partition's share of the epoch is the epoch time itself
            # plus whatever its ``partition_step`` fault point added
            # (slow_rank's injected sleep lands HERE, in exactly one
            # partition's measured time — the straggler chaos oracle)
            alive_now = elastic.alive_partitions(self.dist.partitions)
            part_seconds = {}
            for p in alive_now:
                tp = get_time()
                fault_point("partition_step", epoch=epoch, partition=p)
                part_seconds[p] = dt + (get_time() - tp)
            if self._straggler is not None:
                self._straggler.observe_epoch(epoch, part_seconds)
        if self._liveness is not None:
            # per-partition heartbeats into the obs stream + miss-K /
            # collective-timeout detection — after the epoch's
            # telemetry (the loss is visible in the stream first),
            # BEFORE ckpt_epoch_end: the raise lands at the rollback
            # boundary the supervisor replans at, and the detection
            # epoch never persists
            self._liveness.epoch_end(
                epoch,
                alive=elastic.alive_partitions(self.dist.partitions),
                step_seconds=device_s,
                partition_seconds=part_seconds,
            )

    def run(self) -> Dict[str, Any]:
        cfg = self.cfg
        self.open_run_root()
        with self.stage("run_begin"):
            key = jax.random.PRNGKey(self.seed + 1)
            log.info(
                "GNNmini::Engine[Dist.%s.GCNimpl] %d partitions, [%d] Epochs",
                jax.default_backend(), self.dist.partitions,
                cfg.epochs,
            )
        with self.stage("ckpt_begin"):
            start_epoch = self.ckpt_begin()
        loss = None
        # rank-health monitor (resilience/elastic): one per attempt — a
        # supervised retry (or a replan, which renumbers the survivors)
        # re-enters run() and gets fresh miss counters for the new plan
        self._liveness = (
            elastic.LivenessMonitor(self.dist.partitions)
            if elastic.elastic_enabled() else None
        )
        # straggler analytics (obs/skew): per-partition epoch timings ->
        # advisory ``straggler`` records. Follows the elastic arming by
        # default; NTS_STRAGGLER=1/0 forces it either way
        self._straggler = (
            skew.StragglerDetector(
                self.dist.partitions, registry=self.metrics,
                on_straggler=elastic.note_straggler,
            )
            if skew.straggler_enabled(default=self._liveness is not None)
            else None
        )
        if self._ring_plan is not None and os.environ.get(
            "NTS_OVERLAP_PROBE", "0"
        ) == "1":
            try:
                self._run_overlap_probe()
            except Exception as e:
                # telemetry must never kill a run: the probe's three extra
                # compiles can fail (OOM, XLA) where training would not
                log.warning("overlap probe failed (%s); continuing "
                            "without ring.probe_* gauges", e)
        # steady-state trace window (see FullBatchTrainer.run)
        from neutronstarlite_tpu.utils.profiling import maybe_trace

        trace_from = start_epoch + 1
        trace_cm = None
        for epoch in range(start_epoch, cfg.epochs):
            if epoch == trace_from and epoch < cfg.epochs:
                trace_cm = maybe_trace(type(self).__name__)
                trace_cm.__enter__()
            with self.epoch_span(epoch):
                with self.stage("epoch_key", epoch):
                    ekey = jax.random.fold_in(key, epoch)
                with self.stage("step_dispatch", epoch) as s_disp:
                    step_args = (
                        self.params,
                        self.opt_state,
                        self.blocks,
                        self.feature_p,
                        self.label_p,
                        self.train01_p,
                        self.valid_p,
                        ekey,
                    )
                    stats_dev = None
                    if self._train_step_stats is not None:
                        # NTS_NUMERICS=1: same math, one extra all-scalar
                        # output
                        (self.params, self.opt_state, loss, _,
                         stats_dev) = self._train_step_stats(*step_args)
                    else:
                        self.params, self.opt_state, loss, _ = (
                            self._train_step(*step_args)
                        )
                with self.stage("step_device", epoch) as s_dev:
                    jax.block_until_ready(loss)
                with self.stage("loss_fetch", epoch):
                    self.maybe_emit_numerics(epoch, stats_dev)
                    if self._quant_probe_fn is not None:
                        self._emit_quant_probe(epoch)
                    # chaos hook (NTS_FAULT_SPEC): nan_loss/stall/crash fire
                    # here, before the loss reaches history, guards, or a
                    # checkpoint
                    loss = fault_point("epoch_loss", epoch=epoch, value=loss)
                    dt = get_time() - s_disp.t0
                    self.epoch_times.append(dt)
                    self.loss_history.append(float(loss))
                with self.stage("epoch_emit", epoch):
                    self._emit_dist_epoch(
                        epoch, dt, loss, s_disp.dur_s, s_dev.dur_s
                    )
                    if (
                        epoch % max(1, cfg.epochs // 20) == 0
                        or epoch == cfg.epochs - 1
                    ):
                        log.info("Epoch %d loss %f", epoch, float(loss))
                with self.stage("ckpt_epoch_end", epoch):
                    self.ckpt_epoch_end(epoch)

        if trace_cm is not None:
            trace_cm.__exit__(None, None, None)
        with self.stage("ckpt_final"):
            self.ckpt_final()
        if self.skip_final_eval(loss):  # benchmark mode, ToolkitBase docs
            accs = {"train": None, "eval": None, "test": None}
        else:
            with self.stage("final_eval"):
                with self.stage("eval_forward"):
                    logits_p = self._eval_logits(
                        self.params, self.blocks, self.feature_p,
                        self.valid_p, key,
                    )
                with self.stage("host_accuracy"):
                    accs = self.dist_eval_report(
                        logits_p, self.label_p, self.mask_p, self.valid_p
                    )
        avg = self.avg_epoch_time()
        log.info("--avg epoch time %.4f s", avg)
        import os as _os

        if _os.environ.get("NTS_DEBUGINFO", "0") == "1":
            log.info("%s", self.debug_info(key))
        # loss is None when a checkpoint restore resumed at/after cfg.epochs
        # (zero epochs ran): still report the restored model's accuracy
        result = {
            "loss": float(loss) if loss is not None else float("nan"),
            "acc": accs,
            "avg_epoch_s": avg,
        }
        self.finalize_metrics(result)
        return result


@register_algorithm("GCNEAGERDIST", "GCNDISTEAGER", "GCNEAGERTPUDIST")
class DistGCNEagerTrainer(DistGCNTrainer):
    """The reference's distributed eager GCN (GCN_EAGER.hpp; order swap at
    GCN_CPU_EAGER.hpp:200-206): per layer, NN first, THEN the cross-partition
    exchange — wire traffic and aggregation both run at the post-matmul
    width (602->128 on the Reddit stack), cutting the dominant exchange cost
    ~d_in/d_out-fold when layers narrow."""

    eager = True
