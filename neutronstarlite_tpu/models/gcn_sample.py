"""Mini-batch sampled GCN (the GCN_CPU_SAMPLE toolkit).

Reference (toolkits/GCN_CPU_SAMPLE.hpp): per epoch, reservoir-sample all
batches (:191-195); per batch, gather input features/labels by sampled ids,
run one MiniBatchFuseOp + NN per hop (:208-223), then loss/backward/update
per batch (:224-229); train/val/test samplers are built from mask nids
(:251-265). Model sync is only the per-update gradient allreduce (here: the
replicated-parameter psum under pjit when a mesh is used).

TPU shape discipline: every batch is padded to the same capacities
(sample/sampler.py), so ``_train_batch`` compiles once and replays for every
batch of every epoch.

Sample/compute overlap: the reference pipelines host-side sampling with
device compute via threads; here JAX's async dispatch does it structurally —
``_train_batch`` returns before the device finishes, so the host samples
batch i+1 (native reservoir sampler) while the chip trains on batch i. The
per-batch device dependency is only the params chain; the single sync point
is the epoch-end ``block_until_ready``.
"""

from __future__ import annotations

from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from neutronstarlite_tpu.models.base import ToolkitBase, register_algorithm
from neutronstarlite_tpu.resilience.faults import fault_point
from neutronstarlite_tpu.nn.layers import dropout
from neutronstarlite_tpu.nn.param import (
    AdamConfig,
    adam_init,
    adam_update,
    xavier_uniform,
)
from neutronstarlite_tpu.ops.minibatch import get_feature, get_label, minibatch_gather
from neutronstarlite_tpu.sample.sampler import SampledBatch, Sampler
from neutronstarlite_tpu.utils.logging import get_logger
from neutronstarlite_tpu.utils.timing import get_time

log = get_logger("gcn_sample")


def _batch_arrays(b: SampledBatch):
    """Flatten a SampledBatch into jit-friendly device arrays."""
    return (
        [jnp.asarray(n) for n in b.nodes],
        [(jnp.asarray(h.src_local), jnp.asarray(h.dst_local), jnp.asarray(h.weight))
         for h in b.hops],
        jnp.asarray(b.seed_mask),
        jnp.asarray(b.seeds),
    )


@register_algorithm("GCNSAMPLESINGLE", "GCNSAMPLE", "GCNCPUSAMPLE")
class GCNSampleTrainer(ToolkitBase):
    weight_mode = "gcn_norm"
    # sampling reads the HOST CSC (the FullyRepGraph analog); the device only
    # ever sees padded batch subgraphs — uploading the full edge set to HBM
    # would waste gigabytes at Reddit scale for arrays never touched
    needs_device_graph = False
    # SAMPLE_PIPELINE (sample/pipeline.py): sync | pipelined | device |
    # fused (sample/fused.py: whole epochs as one scanned dispatch)
    supports_sample_pipeline = True

    def _finalize_datum(self) -> None:
        # the training batch stream (sample/parallel.py) forks its
        # persistent worker pool — that must happen BEFORE the first JAX
        # backend touch (build_model, called by the base method):
        # forking after PJRT's runtime threads exist risks a deadlocked
        # child (module docstring's fork-safety note)
        cfg = self.cfg
        sizes = cfg.layer_sizes()
        fanouts = cfg.fanouts()
        if not fanouts:
            raise ValueError("GCNSAMPLE requires FANOUT in the cfg")
        # the cfg may list more fanout entries than NN layers (gcn_cora_sample
        # ships FANOUT:5-10-10 with LAYERS:1433-256-7); use the last n_layers
        n_layers = len(sizes) - 1
        self.fanouts = fanouts[-n_layers:]
        from neutronstarlite_tpu.sample.parallel import ParallelEpochSampler
        from neutronstarlite_tpu.sample.pipeline import resolve_sample_pipeline

        # SAMPLE_PIPELINE / NTS_SAMPLE_PIPELINE (sample/pipeline.py):
        # sync keeps the in-loop host sampler (the parity oracle);
        # pipelined prefetches deterministic batches + async H2D on a
        # background thread; device additionally draws each hop on-device
        self.sample_mode = resolve_sample_pipeline(cfg)
        hop_sampler = None
        if self.sample_mode in ("device", "fused"):
            # the device table upload is a JAX backend touch, which is
            # fine here: both modes sample inline (no forked pool); the
            # fused epoch scan reads the SAME resident neighbor table
            from neutronstarlite_tpu.sample.device_sampler import (
                DeviceUniformSampler,
            )

            hop_sampler = DeviceUniformSampler.from_host(self.host_graph)
            log.info(
                "SAMPLE_PIPELINE:%s — on-device uniform hop sampler "
                "(neighbor table [%d, %d], %d pre-thinned vertices)",
                self.sample_mode, self.host_graph.v_num, hop_sampler.width,
                hop_sampler.thinned,
            )
        # one object for every worker count (workers=0 runs inline): the
        # per-(epoch, index) seeding makes the batch sequence bit-identical
        # regardless, so worker count is a pure throughput knob
        self.par_sampler = ParallelEpochSampler(
            self.host_graph,
            np.where(self.datum.mask == 0)[0],
            cfg.batch_size,
            self.fanouts,
            seed=self.seed,
            hop_sampler=hop_sampler,
        )
        self.sample_workers = self.par_sampler.workers
        self._last_sample_s = 0.0
        super()._finalize_datum()

    def build_model(self) -> None:
        cfg = self.cfg
        sizes = cfg.layer_sizes()
        n_layers = len(sizes) - 1  # self.fanouts set in _finalize_datum
        key = jax.random.PRNGKey(self.seed)
        params = []
        for i in range(n_layers):
            key, sub = jax.random.split(key)
            params.append({"W": xavier_uniform(sub, sizes[i], sizes[i + 1])})
        self.params = params
        self.adam_cfg = AdamConfig(
            alpha=cfg.learn_rate,
            weight_decay=cfg.weight_decay,
            decay_rate=cfg.decay_rate,
            decay_epoch=cfg.decay_epoch,
        )
        self.opt_state = adam_init(self.params)

        # train/val/test samplers from mask nids (GCN_CPU_SAMPLE.hpp:251-265);
        # eval streams are sequential (shuffle=False), training batches come
        # from self.par_sampler above
        self.samplers = {
            which: Sampler(
                self.host_graph,
                np.where(self.datum.mask == which)[0],
                cfg.batch_size,
                self.fanouts,
                seed=self.seed + which,
            )
            for which in (0, 1, 2)
        }
        drop_rate = cfg.drop_rate
        adam_cfg = self.adam_cfg
        caps = self.samplers[0].node_caps
        # PRECISION:bfloat16 — same policy as the full-batch models
        # (models/gcn.py): feature gather + matmuls in bf16, parameters and
        # returned logits stay float32 (edge weights stay f32, so the
        # per-batch segment sum accumulates wide)
        compute_dtype = jnp.bfloat16 if cfg.precision == "bfloat16" else None

        def cast(a):
            return a.astype(compute_dtype) if compute_dtype is not None else a

        def batch_forward(params, feature, nodes, hops, key, train):
            x = cast(get_feature(feature, nodes[0]))
            for i, (p, (src_l, dst_l, w)) in enumerate(zip(params, hops)):
                agg = minibatch_gather(src_l, dst_l, w, x, caps[i + 1])
                h = cast(agg) @ cast(p["W"])
                if i < len(params) - 1:
                    h = jax.nn.relu(h)
                    if train:
                        h = dropout(jax.random.fold_in(key, i), h, drop_rate, train)
                x = h
            return x.astype(jnp.float32)  # [B, n_classes]

        def batch_loss(params, feature, label, nodes, hops, seed_mask, seeds, key):
            logits = batch_forward(params, feature, nodes, hops, key, True)
            target = get_label(label, seeds)
            logp = jax.nn.log_softmax(logits, axis=-1)
            picked = jnp.take_along_axis(logp, target[:, None], axis=-1)[:, 0]
            return -(picked * seed_mask).sum() / jnp.maximum(seed_mask.sum(), 1.0)

        @jax.jit
        def train_batch(params, opt_state, feature, label, nodes, hops,
                        seed_mask, seeds, key):
            loss, grads = jax.value_and_grad(batch_loss)(
                params, feature, label, nodes, hops, seed_mask, seeds, key
            )
            params, opt_state = adam_update(params, grads, opt_state, adam_cfg)
            return params, opt_state, loss

        @jax.jit
        def eval_batch(params, feature, nodes, hops, key):
            return batch_forward(params, feature, nodes, hops, key, False)

        self._train_batch = train_batch
        self._train_step = train_batch  # uniform tools/aot_check hook name
        self._eval_batch = eval_batch

        # numerics plane (obs/numerics, NTS_NUMERICS=1): the stats-fused
        # per-batch variant (params/grads groups + the global grad norm;
        # the default train_batch above stays byte-identical). run()
        # keeps the LAST batch's stats output per epoch and fetches it
        # on the NTS_NUMERICS_EVERY cadence.
        from neutronstarlite_tpu.obs import numerics

        self._numerics_on = numerics.numerics_enabled()
        self._train_batch_stats = None
        if self._numerics_on:
            @jax.jit
            def train_batch_stats(params, opt_state, feature, label, nodes,
                                  hops, seed_mask, seeds, key):
                loss, grads = jax.value_and_grad(batch_loss)(
                    params, feature, label, nodes, hops, seed_mask, seeds,
                    key,
                )
                new_params, new_opt = adam_update(
                    params, grads, opt_state, adam_cfg
                )
                stats = numerics.step_stats(
                    params=new_params, grads=grads
                )
                return new_params, new_opt, loss, stats

            self._train_batch_stats = train_batch_stats

        # live wire counters (obs): the minibatch path's data movement is
        # the host->device gather of the padded input-node feature rows
        # (capacity, not realized rows — the shape actually shipped).
        # Priced at the STORED table dtype: the gather reads f32 rows and
        # only the post-gather cast narrows, so bf16 runs move the same
        # bytes here
        itemsize = int(np.dtype(self.datum.feature.dtype).itemsize)
        self._gather_bytes_per_batch = caps[0] * sizes[0] * itemsize
        self.metrics.gauge_set(
            "wire.feature_gather_bytes_per_batch",
            self._gather_bytes_per_batch,
        )
        # sample.h2d_bytes accounting (single-definition formula,
        # tools/wire_accounting): the sync path ships one padded batch
        # payload per step; the pipeline producer MEASURES the same
        # number per staged batch; fused ships nothing per batch
        from neutronstarlite_tpu.tools.wire_accounting import (
            sample_batch_payload_bytes,
        )

        self._sample_payload_bytes = sample_batch_payload_bytes(
            caps, self.fanouts
        )

        # SAMPLE_PIPELINE:fused (sample/fused.py): whole epochs run as
        # ONE AOT-compiled lax.scan over the resident neighbor/degree
        # tables — draw -> remap -> gather -> train per batch with zero
        # per-batch H2D. The step math is the SAME batch_loss +
        # adam_update composition train_batch jits (draws are
        # distribution-equivalent to the host sampler, docs/SAMPLING.md)
        self._fused = None
        if self.sample_mode == "fused":
            from neutronstarlite_tpu.sample.fused import (
                FusedEpochRunner,
                degree_tables,
            )

            hs = self.par_sampler.hop_sampler
            tables = (hs.nbr, hs.eff_deg) + degree_tables(self.host_graph)
            numerics_on = self._numerics_on

            def fused_step(params, opt_state, feature, label, nodes,
                           hops, seed_mask, seeds, key):
                loss, grads = jax.value_and_grad(batch_loss)(
                    params, feature, label, nodes, hops, seed_mask,
                    seeds, key,
                )
                params, opt_state = adam_update(
                    params, grads, opt_state, adam_cfg
                )
                if numerics_on:
                    stats = numerics.step_stats(params=params, grads=grads)
                    return params, opt_state, loss, stats
                return params, opt_state, loss

            self._fused = FusedEpochRunner(
                fused_step, caps, self.fanouts, cfg.batch_size, tables,
                np.where(self.datum.mask == 0)[0],
                metrics=self.metrics, has_stats=numerics_on,
            )

    def aot_args(self):
        """The exact argument tuple run() passes to the jitted per-batch
        train step (tools/aot_check lowers it for a topology without
        executing). One host-side sample supplies the padded batch arrays —
        their shapes are static (node_caps from FANOUT x BATCH_SIZE), so any
        batch is shape-representative."""
        b = next(self.samplers[0].sample_epoch(shuffle=False))
        nodes, hops, seed_mask, seeds = _batch_arrays(b)
        return (
            self.params, self.opt_state, self.feature, self.label,
            nodes, hops, seed_mask, seeds, jax.random.PRNGKey(self.seed + 1),
        )

    def _evaluate(self, which: int, key) -> float:
        correct = total = 0
        for b in self.samplers[which].sample_epoch(shuffle=False):
            nodes, hops, seed_mask, seeds = _batch_arrays(b)
            logits = np.asarray(
                self._eval_batch(self.params, self.feature, nodes, hops, key)
            )
            real = b.seed_mask > 0
            pred = logits.argmax(axis=1)[real]
            target = self.datum.label[b.seeds[real]]
            correct += int((pred == target).sum())
            total += int(real.sum())
        acc = correct / max(total, 1)
        name = {0: "Train", 1: "Eval", 2: "Test"}[which]
        log.info("%s Acc: %f %d %d", name, acc, total, correct)
        return acc

    def _epoch_batches(self, epoch: int, pipeline):
        """One epoch's device-ready batch tuples + the sample-time split.

        Yields ``(nodes, hops, seed_mask, seeds)``; afterwards
        ``self._last_sample_s`` holds the host time this epoch spent
        WAITING on sampling — the full serial sample+convert time on the
        sync path, the residual queue stall on the pipelined path (the
        number the overlap is supposed to shrink)."""
        if pipeline is not None:
            yield from pipeline.epoch_stream(epoch)
            self._last_sample_s = pipeline.last_epoch_stall_s
            return
        sample_s = 0.0
        it = iter(self.par_sampler.sample_epoch(epoch))
        while True:
            t0 = get_time()
            try:
                b = next(it)
            except StopIteration:
                break
            arrays = _batch_arrays(b)
            sample_s += get_time() - t0
            yield arrays
        self._last_sample_s = sample_s

    def _after_epoch(self, epoch: int, t0: float, losses, stats_dev,
                     dispatch_s: float, device_s: float) -> List[float]:
        """Shared epoch-end stages for the per-batch and fused
        (one-dispatch) loops; returns the epoch's per-batch losses as host
        floats. ``loss_fetch``: numerics/chaos hooks, the losses' fetch
        (``losses`` is the scan's device vector when fused, a list of
        device scalars otherwise), loss history. ``epoch_emit``: the
        sampling counters — ``sample.h2d_bytes`` priced per batch on the
        sync path (the wire_accounting formula), producer-MEASURED when
        pipelined/device, and exactly 0 when fused — and the typed
        epoch/epoch_scan records. ``ckpt_epoch_end``: the epoch-boundary
        checkpoint hook (for fused runs this IS the scan boundary)."""
        cfg = self.cfg
        fused = self._fused is not None
        with self.stage("loss_fetch", epoch):
            self.maybe_emit_numerics(epoch, stats_dev)
            losses = [
                float(l) for l in (np.asarray(losses) if fused else losses)
            ]
            # chaos hook (NTS_FAULT_SPEC): nan_loss/stall/crash fire
            # here, before the loss reaches history or the guards
            epoch_loss = fault_point(
                "epoch_loss", epoch=epoch, value=float(np.mean(losses)),
            )
            dt = get_time() - t0
            self.epoch_times.append(dt)
            self.loss_history.append(float(epoch_loss))
        with self.stage("epoch_emit", epoch):
            # fused gathers features on-device from the resident slab: the
            # wire gather AND the per-batch H2D payload are structurally 0
            gather_bytes = (
                0 if fused else len(losses) * self._gather_bytes_per_batch
            )
            if self.sample_mode in ("sync", "fused"):
                # pipelined/device measure this per staged batch in the
                # producer (sample/pipeline.py); sync prices the formula
                h2d = (
                    0 if fused else len(losses) * self._sample_payload_bytes
                )
                self.metrics.counter_add("sample.h2d_bytes", h2d)
            self.metrics.counter_add("sample.batches", len(losses))
            self.metrics.counter_add(
                "wire.feature_gather_bytes", gather_bytes
            )
            if fused:
                self.metrics.event(
                    "epoch_scan", bucket=int(self._fused.n_batches),
                    batches=len(losses), dispatches=1, h2d_bytes=0,
                    epoch=int(epoch), seconds=round(dt, 6),
                )
            # the host-observable epoch split: step_dispatch = the live
            # span over issuing the epoch's device steps (ONE scan dispatch
            # when fused; the whole batch loop otherwise, the host's
            # sampling included), sample_wait = the part of it blocked on
            # sampling (serial sample time when sync; residual pipeline
            # stall when pipelined; 0 when fused — sampling is inside the
            # scan), step_device = the epoch-end wait for the device to
            # drain
            stages = {
                "sample_wait": self._last_sample_s,
                "step_dispatch": dispatch_s,
                "step_device": device_s,
            }
            self.emit_epoch(
                epoch, dt, self.loss_history[-1], stages=stages,
                batches=len(losses), feature_gather_bytes=gather_bytes,
            )
        if (
            epoch % max(1, cfg.epochs // 10) == 0
            or epoch == cfg.epochs - 1
        ):
            log.info(
                "Epoch %d loss %f (%d batches)",
                epoch, self.loss_history[-1], len(losses),
            )
        with self.stage("ckpt_epoch_end", epoch):
            self.ckpt_epoch_end(epoch)
        return losses

    def run(self) -> Dict[str, Any]:
        cfg = self.cfg
        self.open_run_root()
        with self.stage("run_begin"):
            key = jax.random.PRNGKey(self.seed + 1)
            log.info(
                "GNNmini::Engine[%s.GCNSampleimpl] B=%d fanout=%s [%d] Epochs "
                "(%d sample workers, sampling %s)",
                jax.default_backend(), cfg.batch_size, self.fanouts, cfg.epochs, self.sample_workers,
                self.sample_mode,
            )
        loss = None
        # checkpoint/resume parity with the full-batch and dist trainers
        # (base.ckpt_* hooks) — also what hands trained weights to serve/:
        # the inference engine restores exactly these step dirs
        with self.stage("ckpt_begin"):
            start_epoch = self.ckpt_begin()
        pipeline = None
        if self.sample_mode in ("pipelined", "device") \
                and start_epoch < cfg.epochs:
            from neutronstarlite_tpu.sample.pipeline import SamplePipeline

            # fresh pipeline per run(): a supervised retry re-enters here
            # and must re-schedule from its rollback epoch
            pipeline = SamplePipeline(
                self.par_sampler, range(start_epoch, cfg.epochs),
                metrics=self.metrics, tracer=self.tracer,
            )
        try:
            for epoch in range(start_epoch, cfg.epochs):
                with self.epoch_span(epoch) as espan:
                    stats_dev = None
                    if self._fused is not None:
                        # ONE dispatch: shuffle + per-batch draw/remap/
                        # gather/train all inside the scanned program; the
                        # epoch-end block is the only sync point and the
                        # ckpt/numerics hooks run at this scan boundary
                        with self.stage("step_dispatch", epoch) as s_disp:
                            (self.params, self.opt_state, losses,
                             stats_dev) = self._fused.run_epoch(
                                self.params, self.opt_state, self.feature,
                                self.label, epoch, key,
                            )
                        with self.stage("step_device", epoch) as s_dev:
                            jax.block_until_ready(losses)
                        self._last_sample_s = 0.0
                    else:
                        losses = []
                        with self.stage("step_dispatch", epoch) as s_disp:
                            for bi, (nodes, hops, seed_mask, seeds) in \
                                    enumerate(
                                        self._epoch_batches(epoch, pipeline)
                                    ):
                                bkey = jax.random.fold_in(
                                    key, epoch * 100003 + bi
                                )
                                if self._train_batch_stats is not None:
                                    # NTS_NUMERICS=1: same math, one extra
                                    # scalar output — the epoch keeps the
                                    # LAST batch's stats
                                    (self.params, self.opt_state, loss,
                                     stats_dev) = self._train_batch_stats(
                                        self.params, self.opt_state,
                                        self.feature, self.label, nodes,
                                        hops, seed_mask, seeds, bkey,
                                    )
                                else:
                                    self.params, self.opt_state, loss = (
                                        self._train_batch(
                                            self.params, self.opt_state,
                                            self.feature, self.label, nodes,
                                            hops, seed_mask, seeds, bkey,
                                        )
                                    )
                                losses.append(loss)
                        with self.stage("step_device", epoch) as s_dev:
                            jax.block_until_ready(loss)
                    losses = self._after_epoch(
                        epoch, espan.t0, losses, stats_dev,
                        s_disp.dur_s, s_dev.dur_s,
                    )
                    loss = losses[-1] if losses else loss
        finally:
            # drain on ANY exit — early stop, guard trip, worker fault —
            # so no producer thread outlives its epoch loop
            if pipeline is not None:
                pipeline.close()
        with self.stage("ckpt_final"):
            self.ckpt_final()
        # training is done: release the sampling worker pool (a sweep that
        # builds many trainers must not accumulate forked children; a
        # second run() on the same trainer samples inline, same batches)
        self.par_sampler.close()
        with self.stage("final_eval"):
            accs = {
                "train": self._evaluate(0, key),
                "eval": self._evaluate(1, key),
                "test": self._evaluate(2, key),
            }
        avg = float(np.mean(self.epoch_times[1:])) if len(self.epoch_times) > 1 else 0.0
        log.info("--avg epoch time %.4f s", avg)
        # loss is None when a checkpoint restore resumed at/after cfg.epochs
        # (zero epochs ran): still report the restored model's accuracy
        result = {
            "loss": float(loss) if loss is not None else float("nan"),
            "acc": accs,
            "avg_epoch_s": avg,
        }
        self.finalize_metrics(result)
        return result
