"""Distributed GCN over the compacted mirror exchange, with DepCache.

The TPU completion of the reference's cached GPU engine
``sync_compute_decoupled_from_cached`` (core/graph.hpp:3723) + ``FeatureCache``
(core/NtsScheduler.hpp:556-637): GCN where each layer materializes mirror rows
through the fixed-capacity slot exchange (parallel/mirror.py) and hot rows are
served from local HBM instead of the interconnect
(parallel/feature_cache.py):

- **layer 0** aggregates raw input features, which are constant across
  epochs, so hot mirror rows are *replicated* once at preprocessing — exact,
  zero communication for the cached fraction, every epoch;
- **deeper layers** aggregate activations that change per epoch; with
  ``CACHE_REFRESH: R`` > 1 hot rows are served from a *historical* cache
  refilled every R epochs by an eval-mode forward (dropout off — caching a
  train step's activations would freeze one epoch's dropout mask into the
  hot rows for R-1 epochs). Gradients don't flow through stale rows, the
  standard historical-embedding trade. R = 1 (default) fetches fresh every
  epoch — pure "communication" mode, exact.

Enable with ``PROC_REP: 1`` + ``REP_THRESHOLD: d`` (cache rows whose source
out-degree >= d; the reference's replication_threshold, core/graph.hpp:179).
With PROC_REP off this trainer is the plain compacted-mirror GCN — the
communication-only point of the reference's communication/replication/caching
design space.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as PS

from neutronstarlite_tpu.models.base import ToolkitBase, register_algorithm
from neutronstarlite_tpu.resilience.faults import fault_point
from neutronstarlite_tpu.models.gcn import init_gcn_params
from neutronstarlite_tpu.models.gcn_dist import gcn_layer_nn
from neutronstarlite_tpu.nn.layers import batch_norm_apply, dropout
from neutronstarlite_tpu.nn.param import AdamConfig, adam_init, adam_update
from neutronstarlite_tpu.parallel import dist_edge_ops as deo
from neutronstarlite_tpu.parallel import feature_cache as fc
from neutronstarlite_tpu.parallel.feature_cache import CachedMirrorGraph
from neutronstarlite_tpu.parallel.mesh import PARTITION_AXIS
from neutronstarlite_tpu.utils.logging import get_logger
from neutronstarlite_tpu.utils.timing import get_time

log = get_logger("gcn_dist_cache")


def _extract_hot(cmg: CachedMirrorGraph, mirrors: jax.Array) -> jax.Array:
    """Slice the hot slots out of a full mirror tensor — the cache fill
    inside the eval-mode refresh forward. [P, P*mb, f] -> [P, P*mc, f]."""
    P, mb, mc = cmg.partitions, cmg.mb, cmg.mc
    f = mirrors.shape[-1]
    return mirrors.reshape(P, P, mb, f)[:, :, :mc].reshape(P, P * mc, f)


def _materialize(mesh, cmg, tables, cache_tables, x, cached_rows):
    """Mirror tensor for one layer: partial fetch when a cache is given,
    full fetch otherwise."""
    if cached_rows is not None and cmg.mc > 0:
        if mesh is None:
            return fc.dist_get_dep_nbr_partial_sim(cmg, x, cached_rows)
        return fc.dist_get_dep_nbr_partial(mesh, cmg, cache_tables[0], x, cached_rows)
    if mesh is None:
        return deo.dist_get_dep_nbr_sim(cmg, x)
    return deo.dist_get_dep_nbr(mesh, cmg, tables, x)


def dist_gcn_cache_forward(
    mesh,
    cmg: CachedMirrorGraph,
    tables,
    cache_tables,
    params,
    x,
    cached0: Optional[jax.Array],
    caches: Optional[List[jax.Array]],
    valid_mask,
    key,
    drop_rate: float,
    train: bool,
    fill_caches: bool,
):
    """Standard GCN order (aggregate -> transform), mirror-exchange variant.

    Returns (logits, new_caches). ``caches[i-1]`` serves layer i's hot rows
    when given; ``fill_caches`` makes full-fetch layers emit their hot slice
    as the new cache (refresh epochs)."""
    n_layers = len(params)
    weight = jnp.asarray(cmg.edge_weight) if mesh is None else tables[3]
    new_caches: List[jax.Array] = []
    for i, layer in enumerate(params):
        cr = cached0 if i == 0 else (caches[i - 1] if caches is not None else None)
        mir = _materialize(mesh, cmg, tables, cache_tables, x, cr)
        if i > 0 and fill_caches:
            # only refresh steps emit caches; returning the input caches on
            # cached steps would round-trip [P, P*mc, f] copies through the
            # jit boundary for nothing
            new_caches.append(_extract_hot(cmg, mir))
        if mesh is None:
            h = deo.dist_aggregate_dst_fuse_weight_sim(cmg, weight, mir)
        else:
            h = deo.dist_aggregate_dst_fuse_weight(mesh, cmg, tables, weight, mir)
        x = gcn_layer_nn(
            i, n_layers, layer, h, x, valid_mask, key, drop_rate, train
        )
    return x, new_caches


@register_algorithm("GCNDISTMIRROR", "GCNDISTCACHE", "GCNDISTREP")
class DistGCNCacheTrainer(ToolkitBase):
    """GCN over the mirror-slot exchange with hybrid dependency management."""

    needs_device_graph = False
    weight_mode = "gcn_norm"
    with_bn = True

    # DIST_PATH/WIRE_DTYPE refusal lives in ToolkitBase._check_dist_path
    # (supports_dist_path stays False: the DepCache exchange is the
    # compacted mirror-slot all_to_all)

    def build_model(self) -> None:
        cfg = self.cfg
        self.mesh, P = self.resolve_mesh()
        if cfg.precision == "bfloat16":
            # loud, not silent: the DepCache exchange keeps f32 (the
            # cached/fetched slot layout has no bf16 form yet); a user
            # expecting the half-wire PRECISION behavior of the other dist
            # trainers must learn the knob did nothing here
            log.warning(
                "PRECISION:bfloat16 is not implemented for the DepCache "
                "trainer (%s); running f32", cfg.algorithm
            )

        # PROC_REP off => threshold above any degree => no hot slots, pure
        # communication; the build degenerates to the plain MirrorGraph.
        # REP_THRESHOLD:auto (-1) => the hybrid decision is made for the
        # user: smallest threshold whose replicated layer-0 rows fit the
        # CACHE_BUDGET_MIB budget (most caching, least wire traffic).
        if not cfg.process_rep:
            threshold = int(self.host_graph.out_degree.max()) + 1
        elif cfg.rep_threshold < 0:
            # the budget must cover EVERYTHING allocated per hot slot: the
            # replicated layer-0 rows [P*mc, f0] plus one historical cache
            # [P*mc, hidden_i] per deep layer (dist_gcn_cache_forward emits
            # caches for layers 1..n-1) — so price the sum of those widths,
            # not just f0
            widths = cfg.layer_sizes()[:-1]
            threshold = CachedMirrorGraph.choose_replication_threshold(
                self.host_graph, P,
                feature_size=sum(widths),
                budget_bytes=cfg.cache_budget_mib << 20,
            )
        else:
            threshold = cfg.rep_threshold
        self.cmg = CachedMirrorGraph.build(self.host_graph, P, threshold)
        self.cache_refresh = max(int(cfg.cache_refresh), 1)
        if self.mesh is not None:
            self.tables = self.cmg.shard(self.mesh)
            self.cache_tables = self.cmg.shard_cache_tables(self.mesh)
        else:
            self.tables = self.cache_tables = None

        pad = self.cmg.pad_vertex_array
        if self.mesh is not None:
            vsh = NamedSharding(self.mesh, PS(PARTITION_AXIS, None))
            vsh1 = NamedSharding(self.mesh, PS(PARTITION_AXIS))
            csh = NamedSharding(self.mesh, PS(PARTITION_AXIS, None, None))
            rsh = NamedSharding(self.mesh, PS())
            put = jax.device_put
        else:
            put = lambda a, s: jnp.asarray(a)
            vsh = vsh1 = csh = rsh = None
        self.feature_p = put(pad(self.datum.feature), vsh)
        self.label_p = put(pad(self.datum.label.astype(np.int32)), vsh1)
        self.valid_p = put(self.cmg.valid_mask(), vsh1)
        train01 = (self.datum.mask == 0).astype(np.float32)
        self.train01_p = put(pad(train01), vsh1)
        # pad fill -1 so padding rows match no mask split in the eval counters
        self.mask_p = put(pad(self.datum.mask, fill=-1), vsh1)

        # layer-0 replication: raw features of hot rows, gathered host-side
        # once — the padded vertex space indexes via pad_vertex_array ids, so
        # replicate from the ORIGINAL [V, f] feature table (cached_global
        # holds original ids).
        if self.cmg.mc > 0:
            self.cached0 = put(self.cmg.replicate_rows(self.datum.feature), csh)
            log.info(
                "DepCache: %d%% of mirror slots replicated (threshold %d, "
                "mc=%d mf=%d vs dense mb=%d)",
                int(100 * self.cmg.cached_fraction),
                threshold,
                self.cmg.mc,
                self.cmg.mf,
                self.cmg.mb,
            )
        else:
            self.cached0 = None
        self.caches: Optional[List[jax.Array]] = None  # deep-layer historical

        key = jax.random.PRNGKey(self.seed)
        params = init_gcn_params(key, cfg.layer_sizes(), with_bn=self.with_bn)
        self.params = jax.tree.map(lambda a: put(a, rsh), params)
        self.adam_cfg = AdamConfig(
            alpha=cfg.learn_rate,
            weight_decay=cfg.weight_decay,
            decay_rate=cfg.decay_rate,
            decay_epoch=cfg.decay_epoch,
        )
        self.opt_state = jax.tree.map(lambda a: put(a, rsh), adam_init(params))

        mesh, cmg = self.mesh, self.cmg
        drop_rate = cfg.drop_rate
        masked_nll = self.masked_nll_loss
        adam_cfg = self.adam_cfg

        # O(E) tables ride the jit boundary as ARGUMENTS (not closures) so
        # they aren't inlined into the HLO as constants.
        def make_step(use_caches: bool):
            # the train step never fills caches (fill_caches=False): refills
            # happen in the separate eval-mode _refresh_caches forward so no
            # dropout realization is frozen into the hot rows
            @jax.jit
            def step(params, opt_state, tables, cache_tables, feature, label,
                     train01, valid, cached0, caches, key):
                def loss_fn(p):
                    logits, _ = dist_gcn_cache_forward(
                        mesh, cmg, tables, cache_tables, p, feature, cached0,
                        caches if use_caches else None, valid, key, drop_rate,
                        True, False,
                    )
                    return masked_nll(logits, label, train01), logits

                (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
                params, opt_state = adam_update(params, grads, opt_state, adam_cfg)
                return params, opt_state, loss

            return step

        self._use_hist = self.cache_refresh > 1 and self.cmg.mc > 0
        self._step_fresh = make_step(False)  # full fetch
        self._step_cached = make_step(True)  # partial fetch

        @jax.jit
        def eval_logits(params, tables, cache_tables, feature, valid, cached0, key):
            logits, _ = dist_gcn_cache_forward(
                mesh, cmg, tables, cache_tables, params, feature, cached0,
                None, valid, key, 0.0, False, False,
            )
            return logits

        self._eval_logits = eval_logits

        # cache refresh runs an EVAL-mode forward (no dropout): caching the
        # train step's activations would freeze one epoch's dropout mask
        # into the hot rows for the next R-1 epochs, biasing them relative
        # to the fresh-fetched rows
        @jax.jit
        def refresh_caches(params, tables, cache_tables, feature, valid, cached0, key):
            _, nc = dist_gcn_cache_forward(
                mesh, cmg, tables, cache_tables, params, feature, cached0,
                None, valid, key, 0.0, False, True,
            )
            return nc

        self._refresh_caches = refresh_caches

        # live wire counters (obs): the DepCache split prices partial
        # fetches at mf rows and full fetches at mb rows per remote chunk
        # (same formula tools/wire_accounting reports offline); the run
        # loop picks per epoch, since refresh epochs re-fetch everything
        from neutronstarlite_tpu.tools.wire_accounting import (
            exchange_rows_per_device,
        )

        vp = getattr(self.cmg, "vp", 0)
        self._wire_widths = cfg.layer_sizes()[:-1]
        self._rows_full = exchange_rows_per_device(
            "mirror", self.cmg.partitions, vp, self.cmg.mb
        )
        self._rows_partial = exchange_rows_per_device(
            "mirror", self.cmg.partitions, vp, self.cmg.mf
        )
        self.metrics.gauge_set("wire.comm_layer", "mirror+depcache")
        self.metrics.gauge_set("wire.rows_per_layer_full", self._rows_full)
        self.metrics.gauge_set(
            "wire.rows_per_layer_partial", self._rows_partial
        )
        self.metrics.gauge_set("wire.simulated", int(self.mesh is None))

    def _epoch_wire_bytes_fwd(self, use_cached: bool, refresh: bool) -> int:
        """Forward exchange bytes for one epoch at the f32 slot layout:
        layer 0 serves hot rows from the exact replica, deep layers from
        the historical cache when active; a refresh epoch adds a
        full-fetch eval forward."""
        widths = self._wire_widths
        l0 = self._rows_partial if self.cached0 is not None else self._rows_full
        deep = self._rows_partial if use_cached else self._rows_full
        n = 4 * (l0 * widths[0] + deep * sum(widths[1:]))
        if refresh:
            n += 4 * self._rows_full * sum(widths)
        return n

    def run(self) -> Dict[str, Any]:
        cfg = self.cfg
        self.open_run_root()
        with self.stage("run_begin"):
            key = jax.random.PRNGKey(self.seed + 1)
            use_hist = self._use_hist
            log.info(
                "GNNmini::Engine[Dist.%s.GCNimpl.cached] %d partitions "
                "(mc=%d mf=%d el=%d), refresh=%d, [%d] Epochs",
                jax.default_backend(), self.cmg.partitions, self.cmg.mc, self.cmg.mf, self.cmg.el,
                self.cache_refresh, cfg.epochs,
            )
        with self.stage("ckpt_begin"):
            start_epoch = self.ckpt_begin()
        loss = None
        for epoch in range(start_epoch, cfg.epochs):
            with self.epoch_span(epoch):
                with self.stage("epoch_key", epoch):
                    ekey = jax.random.fold_in(key, epoch)
                with self.stage("step_dispatch", epoch) as s_disp:
                    refresh = use_hist and (
                        epoch % self.cache_refresh == 0 or self.caches is None
                    )
                    if refresh:
                        self.caches = self._refresh_caches(
                            self.params, self.tables, self.cache_tables,
                            self.feature_p, self.valid_p, self.cached0, ekey,
                        )
                    use_cached = use_hist and self.caches is not None
                    step = (
                        self._step_cached if use_cached else self._step_fresh
                    )
                    self.params, self.opt_state, loss = step(
                        self.params, self.opt_state, self.tables,
                        self.cache_tables, self.feature_p, self.label_p,
                        self.train01_p, self.valid_p, self.cached0,
                        self.caches if use_cached else None, ekey,
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
                        epoch, dt, loss,
                        self._epoch_wire_bytes_fwd(use_cached, refresh),
                        len(self._wire_widths) * (2 if refresh else 1),
                        cache_refresh=bool(refresh),
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
                    self.params, self.tables, self.cache_tables,
                    self.feature_p, self.valid_p, self.cached0, key,
                )
            with self.stage("host_accuracy"):
                accs = self.dist_eval_report(
                    logits_p, self.label_p, self.mask_p, self.valid_p
                )
        avg = self.avg_epoch_time()
        log.info("--avg epoch time %.4f s", avg)
        result = {
            "loss": float(loss) if loss is not None else float("nan"),
            "acc": accs,
            "avg_epoch_s": avg,
        }
        self.finalize_metrics(result)
        return result
