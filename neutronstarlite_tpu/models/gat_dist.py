"""Distributed GAT: the Dist* edge-op chain over the mirror-slot exchange.

Reference chain (toolkits/GAT_CPU_DIST.hpp:185-211 and its decomposed OPTM
variant GAT_CPU_DIST_OPTM.hpp:209-235): ``NN(W)`` -> DistGetDepNbrOp (mirror
fetch over MPI) -> DistScatterSrc/DistScatterDst -> edge NN (leaky_relu) ->
DistEdgeSoftMax -> DistAggregateDst[FuseWeight] -> relu.

TPU design (parallel/dist_edge_ops.py): one all_to_all per layer ships the
compacted mirror payload ``[h || h.a_src]`` (feature rows + the source half
of the decomposed attention score — shipping the scalar with the row saves a
second exchange, the same trick OPTM uses to avoid the [E, 2f] concat); the
edge softmax and aggregation run on each device's dst-sorted local edge list;
parameter gradients psum automatically (replicated params under jit).

``simulate=True`` swaps the shard_map ops for their collective-free vmap
twins so the exact math runs on the single-core CI rig (tests); the sharded
path is exercised by dryrun_multichip and NTS_MULTIDEVICE=1 tests.
"""

from __future__ import annotations

import os
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as PS

from neutronstarlite_tpu.models.base import ToolkitBase, register_algorithm
from neutronstarlite_tpu.resilience.faults import fault_point
from neutronstarlite_tpu.models.gat import LEAKY_SLOPE, init_gat_params
from neutronstarlite_tpu.nn.layers import compute_cast, dropout
from neutronstarlite_tpu.nn.param import AdamConfig, adam_init, adam_update
from neutronstarlite_tpu.parallel import dist_edge_ops as deo
from neutronstarlite_tpu.parallel.mesh import PARTITION_AXIS
from neutronstarlite_tpu.parallel.mirror import MirrorGraph
from neutronstarlite_tpu.utils.logging import get_logger
from neutronstarlite_tpu.utils.timing import get_time

log = get_logger("gat_dist")


def dist_gat_layer(mesh, mg: MirrorGraph, tables, W, a, x, last: bool,
                   nn_only: bool = False, compute_dtype=None):
    """One GAT layer in the distributed edge-op chain. ``mesh=None`` selects
    the simulated (collective-free) ops. ``nn_only`` replaces the whole
    graph-op chain (mirror fetch + edge ops) with a zero aggregate at the
    same shape — DEBUGINFO's nn_time program (models/debuginfo.py).

    ``compute_dtype=jnp.bfloat16`` (PRECISION:bfloat16) runs the matmuls,
    the mirror EXCHANGE and the edge chain in bf16 — the all_to_all ships
    half the bytes, the dist path's dominant wire cost. Parameters stay
    f32, per-dst segment sums accumulate in f32 (the chunked AND
    non-chunked/sim aggregation bodies both upcast), and this path
    re-materializes f32 activations at every layer boundary — stricter
    than the GCN family's policy (models/gcn.py keeps bf16 activations
    between layers and casts once at the end); the edge chain's softmax
    is the numerically delicate part that earns the difference."""
    cast = compute_cast(compute_dtype)
    x = cast(x)
    h = x @ cast(W)  # [P*vp, f'] — local matmul, params replicated
    f = h.shape[1]
    al = h @ cast(a[:f])  # [P*vp, 1] source half of the decomposed attention
    ar = h @ cast(a[f:])  # [P*vp, 1] dst half
    if nn_only:
        # the [f', 1] attention matvecs al/ar may be DCE'd here; they are
        # negligible next to the W matmul, so nn_time stays honest
        out = jnp.zeros_like(h, dtype=jnp.float32)
        return out if last else jax.nn.relu(out)
    payload = jnp.concatenate([h, al], axis=1)
    if mesh is None:
        mir = deo.dist_get_dep_nbr_sim(mg, payload)  # [P, P*Mb, f'+1]
        e_al = deo.dist_scatter_src_sim(mg, mir[:, :, f:])
        e_ar = deo.dist_scatter_dst_sim(mg, ar)
        score = jax.nn.leaky_relu(e_al + e_ar, negative_slope=LEAKY_SLOPE)
        s = deo.dist_edge_softmax_sim(mg, score)
        out = deo.dist_aggregate_dst_fuse_weight_sim(mg, s, mir[:, :, :f])
    elif len(tables) == 7:
        # chunked + rematerialized chain (full-scale HBM fit; the
        # un-chunked form AOT-measured 14.8 of 15.75 GiB at full Reddit)
        out = deo.dist_gated_chain_chunked(
            mesh, mg, tables, payload, ar, f, LEAKY_SLOPE
        )
    else:
        mir = deo.dist_get_dep_nbr(mesh, mg, tables, payload)
        e_al = deo.dist_scatter_src(mesh, mg, tables, mir[:, :, f:])
        e_ar = deo.dist_scatter_dst(mesh, mg, tables, ar)
        score = jax.nn.leaky_relu(e_al + e_ar, negative_slope=LEAKY_SLOPE)
        s = deo.dist_edge_softmax(mesh, mg, tables, score)
        out = deo.dist_aggregate_dst_fuse_weight(mesh, mg, tables, s, mir[:, :, :f])
    out = out.astype(jnp.float32)  # activations between layers stay f32
    return out if last else jax.nn.relu(out)


def dist_gat_forward(mesh, mg, tables, params, x, key, drop_rate: float,
                     train: bool, nn_only: bool = False, compute_dtype=None):
    n = len(params)
    for i, layer in enumerate(params):
        x = dist_gat_layer(
            mesh, mg, tables, layer["W"], layer["a"], x, i == n - 1,
            nn_only=nn_only, compute_dtype=compute_dtype,
        )
        if train and i < n - 1:
            x = dropout(jax.random.fold_in(key, i), x, drop_rate, train)
    return x


def dist_gat_fused_forward(mesh, mg, pair, params, x, key, drop_rate: float,
                           train: bool, nn_only: bool = False,
                           compute_dtype=None):
    """KERNEL:fused_edge — the whole edge chain per layer is ONE ring-
    pipelined fused kernel application (parallel/dist_fused_edge.py): the
    [vp, f'+1] payload circulates hop by hop while the online-softmax
    state stays local, so no [El, f]-shaped edge tensors exist anywhere.
    ``mg`` is unused (no mirror tables on this path); ``pair`` is the
    RingFusedEdgePair riding the jit boundary as the tables argument.
    ``compute_dtype=jnp.bfloat16`` ships a bf16 ring payload (half the
    ICI bytes) while the kernel's state stays f32."""
    from neutronstarlite_tpu.parallel.dist_fused_edge import (
        dist_fused_edge_aggregate,
    )

    cast = compute_cast(compute_dtype)
    x = cast(x)
    n = len(params)
    for i, layer in enumerate(params):
        h = x @ cast(layer["W"])  # [P*vp, f'], params replicated
        f = h.shape[1]
        al = h @ cast(layer["a"][:f])  # decomposed attention halves
        ar = h @ cast(layer["a"][f:])
        if nn_only:
            out = jnp.zeros_like(h, dtype=jnp.float32)
        else:
            out = dist_fused_edge_aggregate(
                mesh, pair, h, al, ar, LEAKY_SLOPE
            )
        out = out.astype(jnp.float32)  # activations between layers stay f32
        x = out if i == n - 1 else jax.nn.relu(out)
        if train and i < n - 1:
            x = dropout(jax.random.fold_in(key, i), x, drop_rate, train)
    return x


@register_algorithm("GATCPUDIST", "GATGPUDIST", "GATDIST", "GATCPUDISTOPTM")
class DistGATTrainer(ToolkitBase):
    """Vertex-sharded full-batch GAT (PARTITIONS cfg key picks the mesh)."""

    needs_device_graph = False
    weight_mode = "ones"  # softmax supplies the edge weights
    # edge-op-chain model hook: forward(mesh, mg, tables, params, x, key,
    # drop_rate, train) — DistGGCNTrainer overrides this and
    # init_model_params only (decoupled graph-op/NN-op split)
    model_forward_fn = staticmethod(dist_gat_forward)
    # KERNEL:fused_edge — the ring-pipelined fused edge kernel
    # (parallel/dist_fused_edge.py); same signature, pair as tables
    fused_forward_fn = staticmethod(dist_gat_fused_forward)
    supports_fused_edge = True

    def init_model_params(self, key):
        return init_gat_params(key, self.cfg.layer_sizes())

    @staticmethod
    def mirror_payload_width(f_out: int) -> int:
        """Columns shipped per mirror row in the per-layer all_to_all:
        GAT's payload is [h || h.a_src] (f'+1); GGCN overrides (2f')."""
        return f_out + 1

    @staticmethod
    def edge_score_channels(f_out: int) -> int:
        """Score-channel width C of the decomposed attention halves (the
        fused kernel's payload/pricing knob): GAT is scalar."""
        return 1

    @classmethod
    def bind_forward(cls, cfg):
        """The forward fn with the cfg's kernel + precision policy bound —
        ONE definition shared by build_model and tools/aot_check, so the
        AOT capacity numbers always measure the program the trainer
        ships."""
        forward = (
            cls.fused_forward_fn
            if cfg.kernel == "fused_edge"
            else cls.model_forward_fn
        )
        if cfg.precision == "bfloat16":
            # PRECISION:bfloat16 — same compute policy as the GCN family:
            # bf16 matmuls + exchange (the all_to_all / ring payload ships
            # half the bytes), f32 params/activations, wide accumulation
            from functools import partial

            forward = partial(forward, compute_dtype=jnp.bfloat16)
        return forward

    def _check_dist_path(self) -> None:
        """KERNEL:fused_edge runs a ring exchange, so DIST_PATH may name
        the ring family (ring_blocked = real collectives, ring_blocked_sim
        = the collective-free CI twin); anything else keeps the base
        refusal (the mirror chain is not a dense-feature DIST_PATH)."""
        cfg = self.cfg
        if cfg.kernel == "fused_edge":
            if cfg.dist_path not in (
                "", "auto", "ring_blocked", "ring_blocked_sim"
            ):
                raise ValueError(
                    f"DIST_PATH:{cfg.dist_path} is not available with "
                    "KERNEL:fused_edge — the fused edge kernel runs the "
                    "ring schedule (ring_blocked / ring_blocked_sim)"
                )
            if getattr(cfg, "wire_dtype", "") or os.environ.get(
                "NTS_WIRE_DTYPE"
            ):
                log.warning(
                    "WIRE_DTYPE/NTS_WIRE_DTYPE is ignored on the fused "
                    "edge ring: the payload ships the compute dtype "
                    "(PRECISION:bfloat16 halves it)"
                )
            return
        super()._check_dist_path()

    def _build_fused_graph(self, P: int):
        """DistGraph partition blocks + the ring fused tables; returns the
        padded-vertex-space provider (the mirror path's MirrorGraph role)."""
        from neutronstarlite_tpu.parallel.dist_fused_edge import (
            RingFusedEdgePair,
        )
        from neutronstarlite_tpu.parallel.dist_graph import DistGraph
        from neutronstarlite_tpu.parallel.dist_ring_blocked import (
            default_ring_vt,
        )

        self.dist = DistGraph.build(self.host_graph, P)
        vt = default_ring_vt(self.dist.vp, self.cfg.kernel_tile)
        pair = RingFusedEdgePair.build(self.dist, vt)
        self.tables = pair.shard(self.mesh) if self.mesh is not None else pair
        self.metrics.gauge_set("kernel.path", "fused_edge")
        self.metrics.gauge_set("kernel.fused_vt", vt)
        # same geometry gauges as the single-chip fused path (fullbatch's
        # _emit_edge_kernel_gauges): levels = stacked level tables across
        # all ring steps, slots = fwd + transposed table capacity
        self.metrics.gauge_set(
            "kernel.fused_levels", sum(len(ls) for ls in pair.fwd.nbr)
        )
        self.metrics.gauge_set(
            "kernel.fused_slots",
            pair.fwd.slot_count() + pair.bwd.slot_count(),
        )
        self.metrics.gauge_set("kernel.edge_hbm_bytes_per_epoch", 0)
        return self.dist

    def build_model(self) -> None:
        cfg = self.cfg
        if cfg.kernel == "fused_edge" and cfg.dist_path == "ring_blocked_sim":
            # the explicit sim spelling forces the collective-free twin
            # (NTS_DIST_SIMULATE=1 parity)
            self.simulate = True
        self.mesh, P = self.resolve_mesh()
        if cfg.kernel == "fused_edge":
            self.mg = None
            space = self._build_fused_graph(P)
            self._finish_build(space)
            return
        self.mg = MirrorGraph.build(self.host_graph, P)
        # the *_sim ops re-derive the tables from mg; only the sharded path
        # consumes device-put tables
        self.tables = None
        if self.mesh is not None:
            # dst-aligned edge chunking for the remat'd gated chain (the
            # full-scale HBM fit — dist_edge_ops.dist_gated_chain_chunked;
            # GGCN inherits). The [P, dp] zero probe carries the static
            # chunk-dst capacity through the jit boundary as a shape.
            # Only need_ids + the chunk tables ship: the uniform [P, El]
            # per-edge tables are dead weight under the chunked chain
            # (~234 MB/device at full Reddit — r5 review).
            from neutronstarlite_tpu.parallel.mirror import chunk_edge_list

            ec = int(os.environ.get("NTS_EDGE_CHUNK", 1_000_000))
            ch = chunk_edge_list(self.mg, ec)
            put = lambda a: jax.device_put(
                jnp.asarray(a),
                NamedSharding(self.mesh, PS(
                    PARTITION_AXIS, *([None] * (np.ndim(a) - 1))
                )),
            )
            self.tables = (
                (put(self.mg.need_ids),)
                + ch.shard(self.mesh)
                + (put(jnp.zeros((self.mg.partitions, ch.dp), jnp.int32)),)
            )
            log.info(
                "gated edge chain: %d chunk(s) x %d edges (dp=%d) — "
                "remat'd per chunk",
                ch.slot.shape[1], ch.slot.shape[2], ch.dp,
            )
        self._finish_build(self.mg)

    def _finish_build(self, space) -> None:
        """The kernel-independent tail of build_model: padded vertex
        arrays, params, wire counters, and the jitted programs. ``space``
        provides the padded vertex space (MirrorGraph on the mirror chain,
        DistGraph on the fused ring)."""
        cfg = self.cfg
        pad = space.pad_vertex_array
        if self.mesh is not None:
            vsh = NamedSharding(self.mesh, PS(PARTITION_AXIS, None))
            vsh1 = NamedSharding(self.mesh, PS(PARTITION_AXIS))
            rsh = NamedSharding(self.mesh, PS())
            put = lambda a, s: jax.device_put(a, s)
        else:
            put = lambda a, s: jnp.asarray(a)
            vsh = vsh1 = rsh = None
        self.feature_p = put(pad(self.datum.feature), vsh)
        self.label_p = put(pad(self.datum.label.astype(np.int32)), vsh1)
        train01 = (self.datum.mask == 0).astype(np.float32)
        self.train01_p = put(pad(train01), vsh1)
        # pad fill -1 so padding rows match no mask split in the eval counters
        self.mask_p = put(pad(self.datum.mask, fill=-1), vsh1)
        self.valid_p = put(space.valid_mask(), vsh1)

        key = jax.random.PRNGKey(self.seed)
        params = self.init_model_params(key)
        self.params = jax.tree.map(lambda a: put(a, rsh), params)
        self.adam_cfg = AdamConfig(
            alpha=cfg.learn_rate,
            weight_decay=cfg.weight_decay,
            decay_rate=cfg.decay_rate,
            decay_epoch=cfg.decay_epoch,
        )
        self.opt_state = jax.tree.map(lambda a: put(a, rsh), adam_init(params))

        # live wire counters (obs): the mirror all_to_all ships the
        # compacted payload rows at each layer's payload width; the fused
        # ring ships (P-1)*vp shard rows of [h || asrc] per layer. Both
        # priced by the row formulas tools/wire_accounting reports
        # offline. ``wire.simulated=1`` marks the collective-free sim
        # rig, where the volume is what WOULD cross a real interconnect.
        from neutronstarlite_tpu.tools.wire_accounting import (
            exchange_rows_per_device,
        )

        sizes = cfg.layer_sizes()
        fused = cfg.kernel == "fused_edge"
        if fused:
            from neutronstarlite_tpu.parallel.dist_fused_edge import (
                fused_wire_cols,
            )

            rows = exchange_rows_per_device("ring", space.partitions, space.vp)
            cols = sum(
                fused_wire_cols(f, type(self).edge_score_channels(f))["fwd"]
                for f in sizes[1:]
            )
        else:
            rows = exchange_rows_per_device(
                "mirror", space.partitions, space.vp, space.mb
            )
            cols = sum(type(self).mirror_payload_width(f) for f in sizes[1:])
        itemsize = 2 if cfg.precision == "bfloat16" else 4
        self._wire_exchanges_per_epoch = len(sizes) - 1
        self._wire_bytes_fwd_per_epoch = rows * cols * itemsize
        self.metrics.gauge_set(
            "wire.comm_layer", "ring_fused" if fused else "mirror"
        )
        self.metrics.gauge_set("wire.rows_per_layer", rows)
        self.metrics.gauge_set(
            "wire.bytes_per_epoch_fwd", self._wire_bytes_fwd_per_epoch
        )
        self.metrics.gauge_set("wire.simulated", int(self.mesh is None))
        if not fused:
            # the eager mirror chain materializes [El, .]-shaped edge
            # tensors per device per layer — the traffic class the fused
            # kernel eliminates (same estimate family as the single-chip
            # gauge: 2 feature-wide passes + 3 score-width passes, f32)
            self.metrics.gauge_set("kernel.path", "eager_edge")
            self.metrics.gauge_set(
                "kernel.edge_hbm_bytes_per_epoch",
                sum(
                    space.el
                    * (2 * f + 3 * type(self).edge_score_channels(f)) * 4
                    for f in sizes[1:]
                ),
            )

        mesh, mg, tables = self.mesh, self.mg, self.tables
        drop_rate = cfg.drop_rate
        masked_nll = self.masked_nll_loss
        adam_cfg = self.adam_cfg
        forward = type(self).bind_forward(cfg)

        # ``tables`` (O(E) sharded slot/dst/weight/mask arrays) rides the
        # jit boundary as an ARGUMENT — closure capture would inline it
        # into the HLO as constants (gigabyte programs at scale). The sim
        # path (tables=None) closes over mg's small numpy tables only.
        @jax.jit
        def train_step(params, opt_state, tables, feature, label, train01, key):
            def loss_fn(p):
                logits = forward(
                    mesh, mg, tables, p, feature, key, drop_rate, True
                )
                return masked_nll(logits, label, train01), logits

            (loss, logits), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
            params, opt_state = adam_update(params, grads, opt_state, adam_cfg)
            return params, opt_state, loss, logits

        @jax.jit
        def eval_logits(params, tables, feature, key):
            return forward(mesh, mg, tables, params, feature, key, 0.0, False)

        self._train_step = train_step
        self._eval_logits = eval_logits

        # DEBUGINFO programs (models/debuginfo.py)
        def _loss(params, tables, feature, label, train01, key,
                  nn_only=False):
            logits = forward(mesh, mg, tables, params, feature, key,
                             drop_rate, True, nn_only=nn_only)
            return masked_nll(logits, label, train01)

        @jax.jit
        def fwd_loss(params, tables, feature, label, train01, key):
            return _loss(params, tables, feature, label, train01, key)

        @jax.jit
        def fwd_nn_only(params, tables, feature, label, train01, key):
            return _loss(params, tables, feature, label, train01, key,
                         nn_only=True)

        @jax.jit
        def fwd_grad(params, tables, feature, label, train01, key):
            return jax.value_and_grad(
                lambda p: _loss(p, tables, feature, label, train01, key)
            )(params)

        self._dbg_fwd = fwd_loss
        self._dbg_nn = fwd_nn_only
        self._dbg_grad = fwd_grad

    def debug_info(self, key, n: int = 3) -> str:
        """Exchange-vs-compute attribution for the dist GAT step (the
        reference dist toolkits' DEBUGINFO, GCN.hpp:308-353 /
        GAT_CPU_DIST.hpp engine timers)."""
        from neutronstarlite_tpu.models.debuginfo import (
            format_dist_report,
            time_median,
        )

        args = (
            self.params, self.tables, self.feature_p, self.label_p,
            self.train01_p, key,
        )
        t_nn = time_median(self._dbg_nn, args, n)
        t_fwd = time_median(self._dbg_fwd, args, n)
        t_grad = time_median(self._dbg_grad, args, n)
        t_step = time_median(
            self._train_step,
            (self.params, self.opt_state, self.tables, self.feature_p,
             self.label_p, self.train01_p, key),
            n,
        )
        return format_dist_report(t_nn, t_fwd, t_grad, t_step)

    def run(self) -> Dict[str, Any]:
        cfg = self.cfg
        self.open_run_root()
        with self.stage("run_begin"):
            key = jax.random.PRNGKey(self.seed + 1)
            if self.mg is not None:
                log.info(
                    "GNNmini::Engine[Dist.%s.GATimpl] %d partitions (Mb=%d El=%d), [%d] Epochs",
                    jax.default_backend(), self.mg.partitions,
                    self.mg.mb,
                    self.mg.el,
                    cfg.epochs,
                )
            else:  # KERNEL:fused_edge — the ring fused tables replace the mirrors
                log.info(
                    "GNNmini::Engine[Dist.%s.GATimpl] %d partitions "
                    "(fused_edge ring, vp=%d), [%d] Epochs",
                    jax.default_backend(), self.dist.partitions, self.dist.vp, cfg.epochs,
                )
        with self.stage("ckpt_begin"):
            start_epoch = self.ckpt_begin()
        loss = None
        for epoch in range(start_epoch, cfg.epochs):
            with self.epoch_span(epoch):
                with self.stage("epoch_key", epoch):
                    ekey = jax.random.fold_in(key, epoch)
                with self.stage("step_dispatch", epoch) as s_disp:
                    self.params, self.opt_state, loss, _ = self._train_step(
                        self.params,
                        self.opt_state,
                        self.tables,
                        self.feature_p,
                        self.label_p,
                        self.train01_p,
                        ekey,
                    )
                with self.stage("step_device", epoch) as s_dev:
                    jax.block_until_ready(loss)
                with self.stage("loss_fetch", epoch):
                    # chaos hook (NTS_FAULT_SPEC): nan_loss/stall/crash fire
                    # here, before the loss reaches history, guards, or a
                    # checkpoint
                    loss = fault_point("epoch_loss", epoch=epoch, value=loss)
                    dt = get_time() - s_disp.t0
                    self.epoch_times.append(dt)
                    self.loss_history.append(float(loss))
                with self.stage("epoch_emit", epoch):
                    self.record_epoch_wire(
                        epoch, dt, loss, self._wire_bytes_fwd_per_epoch,
                        self._wire_exchanges_per_epoch,
                        stages={
                            "step_dispatch": s_disp.dur_s,
                            "step_device": s_dev.dur_s,
                        },
                    )
                    if (
                        epoch % max(1, cfg.epochs // 20) == 0
                        or epoch == cfg.epochs - 1
                    ):
                        log.info("Epoch %d loss %f", epoch, float(loss))
                with self.stage("ckpt_epoch_end", epoch):
                    self.ckpt_epoch_end(epoch)

        with self.stage("ckpt_final"):
            self.ckpt_final()
        with self.stage("final_eval"):
            with self.stage("eval_forward"):
                logits_p = self._eval_logits(
                    self.params, self.tables, self.feature_p, key
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
