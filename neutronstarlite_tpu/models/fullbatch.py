"""Shared full-batch trainer: jitted train step + epoch loop.

Every full-batch toolkit in the reference repeats the same run() skeleton
(epoch loop: Forward, Test(0/1/2), Loss, self_backward, Update — e.g.
GCN_CPU.hpp:232-259, GAT_CPU.hpp, GIN_CPU.hpp). Here the skeleton lives once;
models supply ``init_params`` and ``model_forward``.
"""

from __future__ import annotations

import os
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from neutronstarlite_tpu.models.base import ToolkitBase, _split_counts
from neutronstarlite_tpu.nn.param import AdamConfig, adam_init, adam_update
from neutronstarlite_tpu.resilience.faults import fault_point
from neutronstarlite_tpu.utils.logging import get_logger
from neutronstarlite_tpu.utils.timing import get_time

log = get_logger("fullbatch")


class FullBatchTrainer(ToolkitBase):
    """Template for single-mesh full-batch models (GCN/GAT/GIN/CommNet...)."""

    # models whose only graph op is the fused weighted aggregation run it
    # over the table pair ops/aggregate.build_tables chooses for the cfg
    # (OPTIM_KERNEL:1); GAT rides the ELL pair through the fused attention
    # path (ops/ell_gat, via adapt_ell_graph); GGCN's multi-channel edge
    # chain still needs the CSC edge arrays and keeps DeviceGraph
    supports_optim_kernel = False

    def init_params(self, key):
        raise NotImplementedError

    def model_forward(self, params, graph, x, key, train: bool):
        """[V, f0] -> [V, n_classes] logits.

        ``graph`` (the DeviceGraph pytree) is threaded through the jit
        boundary as an ARGUMENT, never closed over: closure-captured arrays
        are inlined into the HLO as constants, and at Reddit scale that is
        a gigabyte-sized program (remote-compile paths reject it outright).
        """
        raise NotImplementedError

    def adapt_ell_graph(self, compute_graph):
        """Hook: wrap/replace the OPTIM_KERNEL compute graph with
        trainer-specific tables (GAT adds attention slot maps)."""
        return compute_graph

    def forward_taped(self, params, graph, x, key, tap, train=True):
        """Numerics-plane hook: model_forward with a per-layer
        ``tap(i, x) -> x`` threaded through (models/gcn.py implements it
        for the GCN family). None = this model exposes no layer taps —
        the stats step falls back to params/grads/logits groups and the
        provenance replay degrades to an unattributed record."""
        return None

    # trainers whose model_forward consumes cfg.precision (GCN family);
    # the single-chip edge-chain models (GAT/GGCN/GIN/CommNet) run f32 —
    # their op bodies are dtype-polymorphic but the accumulate-wide audit
    # the dist chains got (round 5) has not been done for the single-chip
    # custom_vjps, so the knob warns instead of silently half-applying
    supports_precision = False

    def build_model(self) -> None:
        cfg = self.cfg
        if cfg.precision == "bfloat16" and not type(self).supports_precision:
            log.warning(
                "PRECISION:bfloat16 is not implemented for the single-chip "
                "%s trainer; running f32 (the dist twin supports it)",
                cfg.algorithm,
            )
        self.compute_graph = self.graph
        if self._wants_fused_edge():
            # KERNEL:fused_edge — the blocked streaming fused edge kernel
            # (ops/fused_edge.py). Like the ELL paths, the DeviceGraph
            # edge arrays are dead weight here (base.init_graph already
            # skipped the upload when it saw this path coming).
            self.graph = None
            from neutronstarlite_tpu.ops.fused_edge import FusedEdgePair

            # ELL_LEVELS (cfg or the tune/ autotuner's resolved choice)
            # selects the fused tables' level ladder; "" keeps the path
            # default (binned) via the NTS_ELL_LEVELS env fallback
            with self.timers.phase("tables_build"):
                self.compute_graph = FusedEdgePair.from_host(
                    self.host_graph, vt=cfg.kernel_tile,
                    levels=getattr(cfg, "ell_levels", ""),
                )
            log.info(
                "KERNEL:fused_edge: blocked streaming SDDMM+softmax+SpMM "
                "(%d src tiles of %d, %d fwd levels, %d table slots)",
                self.compute_graph.fwd.n_tiles,
                self.compute_graph.fwd.vt,
                len(self.compute_graph.fwd.nbr),
                self.compute_graph.slot_count(),
            )
        elif self._wants_ell():
            # drop the (unused on this path) DeviceGraph edge arrays BEFORE
            # shipping the tables so peak HBM never holds both O(E)
            # structures (base.init_graph also skips the device upload when
            # it sees this path coming)
            self.graph = None
            from neutronstarlite_tpu.ops.aggregate import build_tables

            with self.timers.phase("tables_build"):
                self.compute_graph, stats = build_tables(cfg, self.host_graph)
            log.info("OPTIM_KERNEL: %s", self.compute_graph.describe())
            if stats is not None:
                self.record_table_stats(stats)
            # trainer-specific table adaptation (e.g. GAT wraps the plain
            # EllPair with the attention slot maps); default is identity
            with self.timers.phase("tables_build"):
                self.compute_graph = self.adapt_ell_graph(self.compute_graph)
        if getattr(type(self), "edge_family", False):
            self._emit_edge_kernel_gauges()
        with self.timers.phase("params_init"):
            key = jax.random.PRNGKey(self.seed)
            self.params = self.init_params(key)
            self.adam_cfg = AdamConfig(
                alpha=cfg.learn_rate,
                weight_decay=cfg.weight_decay,
                decay_rate=cfg.decay_rate,
                decay_epoch=cfg.decay_epoch,
            )
            self.opt_state = adam_init(self.params)
        with self.timers.phase("datum_upload"):
            train_mask01 = jnp.asarray(
                (self.datum.mask == 0).astype(np.float32)
            )
        # first touch uploads the datum (the properties' own datum_upload
        # phases), ahead of step_build, whose cost capture reads them
        self._build_feature()
        _ = self.label, self.mask  # the mask: what run() counts accuracies by
        with self.timers.phase("step_build"):
            self._build_steps(train_mask01)

    def aggregate_input(self, graph, x):
        """[V, f0] features -> layer 0's aggregate [V, f0], traceable: the
        aggregation that ``model_forward`` then leaves out. Implemented by
        the models that answer yes to ``hoists_input_aggregate``."""
        raise NotImplementedError

    def _build_feature(self) -> None:
        """``self.feature``, the step's feature argument: the uploaded
        features or, where the model hoists it, their aggregate, computed
        here once (the ``input_aggregate`` phase). The aggregate is derived
        from the tables and the datum and rebuilt with them, in this one
        place; it is not checkpointed. The raw device table is released
        before the step programs load (the host copy stays in the datum)."""
        self.input_hoisted = self.hoists_input_aggregate()
        self.metrics.gauge_set("agg.input_hoisted", int(self.input_hoisted))
        if not self.input_hoisted:
            _ = self.feature
            return
        self.__dict__.pop("feature", None)  # a re-entry's old aggregate
        self._raw_feature = None
        host = self.host_input_features()
        with self.timers.phase(
            "input_aggregate", width=int(host.shape[1]),
            rows=int(host.shape[0]), bytes=int(host.nbytes),
        ):
            raw = jnp.asarray(host)
            self.feature = jax.block_until_ready(
                jax.jit(self.aggregate_input)(self.compute_graph, raw)
            )
            raw.delete()

    def _build_steps(self, train_mask01) -> None:
        """The jit wrappers run() and the tools dispatch, and the step
        program's cost record (build_model's ``step_build`` phase)."""
        masked_nll = self.masked_nll_loss
        model_forward = self.model_forward
        adam_cfg = self.adam_cfg

        @jax.jit
        def train_step(params, opt_state, graph, feature, label, train01, key):
            def loss_fn(p):
                logits = model_forward(p, graph, feature, key, True)
                return masked_nll(logits, label, train01), logits

            (loss, logits), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
            params, opt_state = adam_update(params, grads, opt_state, adam_cfg)
            return params, opt_state, loss, logits

        @jax.jit
        def eval_logits(params, graph, feature, key):
            return model_forward(params, graph, feature, key, False)

        self._train_mask01 = train_mask01

        self._train_step = train_step
        self._eval_logits = eval_logits

        # DEBUGINFO decomposition (toolkits/GCN.hpp:308-353): separately
        # jitted forward and forward+grad let the breakdown attribute epoch
        # time to forward / backward / optimizer phases
        @jax.jit
        def fwd_only(params, graph, feature, label, train01, key):
            logits = model_forward(params, graph, feature, key, True)
            return masked_nll(logits, label, train01)

        @jax.jit
        def fwd_bwd(params, graph, feature, label, train01, key):
            return jax.value_and_grad(
                lambda p: masked_nll(
                    model_forward(p, graph, feature, key, True), label, train01
                )
            )(params)

        self._fwd_only = fwd_only
        self._fwd_bwd = fwd_bwd

        # NTS_TRACE_STEP=1 runs the epoch as two device programs
        # (forward+backward, then optimizer) so the span timeline gets
        # real per-epoch forward_backward/optim attribution instead of
        # one opaque fused step; jit tracing is lazy, so defining the
        # update program costs nothing unless that mode is on
        @jax.jit
        def optim_step(params, grads, opt_state):
            return adam_update(params, grads, opt_state, adam_cfg)

        self._optim_step = optim_step

        # numerics plane (obs/numerics, NTS_NUMERICS=1): a SECOND jitted
        # step that is the default body plus the tensor-stat tree-reduce
        # as one extra (tiny, all-scalar) output. The default _train_step
        # above is never touched — with numerics off the program that
        # runs is byte-identical to the pre-numerics one (structurally
        # pinned in tests/test_numerics.py), and the stats variant's
        # extra output changes no training math (bitwise loss-curve
        # parity is pinned too).
        from neutronstarlite_tpu.obs import numerics

        self._numerics_on = numerics.numerics_enabled()
        self._train_step_stats = None
        if self._numerics_on:
            has_tap = (
                type(self).forward_taped is not FullBatchTrainer.forward_taped
            )
            forward_taped = self.forward_taped

            @jax.jit
            def train_step_stats(params, opt_state, graph, feature, label,
                                 train01, key):
                def loss_fn(p):
                    # the taps ride the aux output (a closure list would
                    # leak grad-trace tracers out of value_and_grad)
                    acts = []

                    def tap(i, h):
                        acts.append(h)
                        return h

                    if has_tap:
                        logits = forward_taped(p, graph, feature, key, tap)
                    else:
                        logits = model_forward(p, graph, feature, key, True)
                    return masked_nll(logits, label, train01), (logits, acts)

                (loss, (logits, acts)), grads = jax.value_and_grad(
                    loss_fn, has_aux=True
                )(params)
                params, opt_state = adam_update(
                    params, grads, opt_state, adam_cfg
                )
                stats = numerics.step_stats(
                    params=params, grads=grads, acts=acts, logits=logits,
                )
                return params, opt_state, loss, logits, stats

            self._train_step_stats = train_step_stats

        # compiled-program cost attribution (obs/cost): XLA's own
        # FLOPs/bytes for the exact step program run() will dispatch,
        # captured from the lowering (one extra trace, no extra compile)
        from neutronstarlite_tpu.obs.cost import capture_program_cost

        capture_program_cost(
            self.metrics,
            f"fullbatch.train_step/{type(self).__name__}",
            jitted=self._train_step, args=self.aot_args(),
        )

    # score-channel width per output width: GAT's decomposed attention is
    # scalar (C=1); GGCN's per-channel gate overrides with C=f'
    @staticmethod
    def edge_score_channels(f_out: int) -> int:
        return 1

    def _emit_edge_kernel_gauges(self) -> None:
        """``kernel.*`` gauges for the attention/edge families: which
        kernel the chain runs and the estimated per-epoch HBM bytes of
        [Ep, .]-shaped edge tensors it materializes — the traffic the
        fused path eliminates (exactly 0 there; the diff gate in
        scripts/ci_tier1.sh pins that structurally). The eager estimate
        per layer is 2 feature-wide edge passes (the aggregation's gather
        + its backward scatter) plus 3 score-width passes (score,
        softmax, softmax backward), f32."""
        from neutronstarlite_tpu.ops.fused_edge import FusedEdgePair

        cg = self.compute_graph
        sizes = self.cfg.layer_sizes()
        if isinstance(cg, FusedEdgePair):
            path, edge_bytes = "fused_edge", 0
            self.metrics.gauge_set(
                "kernel.fused_levels", len(cg.fwd.nbr)
            )
            self.metrics.gauge_set("kernel.fused_slots", cg.slot_count())
            self.metrics.gauge_set("kernel.fused_vt", cg.fwd.vt)
        else:
            from neutronstarlite_tpu.ops.device_graph import DeviceGraph

            path = "eager_edge" if isinstance(cg, DeviceGraph) else "ell_gat"
            if isinstance(cg, DeviceGraph):
                ep = cg.e_pad
                edge_bytes = sum(
                    ep * (2 * f + 3 * type(self).edge_score_channels(f)) * 4
                    for f in sizes[1:]
                )
            else:
                edge_bytes = 0  # the ELL attention path is edge-tensor-free
        self.metrics.gauge_set("kernel.path", path)
        self.metrics.gauge_set("kernel.edge_hbm_bytes_per_epoch", edge_bytes)

    def debug_info(self, key, n: int = 3) -> str:
        """Per-phase epoch breakdown, DEBUGINFO's role (GCN.hpp:308-353).

        Times the forward, forward+grad, and full step as separate programs
        (warm) and reports forward / backward / update attribution. Enabled
        in run() by NTS_DEBUGINFO=1."""
        args = (
            self.params, self.compute_graph, self.feature, self.label,
            self._train_mask01, key,
        )

        def med(fn, *a):
            jax.block_until_ready(fn(*a))
            ts = []
            for _ in range(n):
                t0 = get_time()
                jax.block_until_ready(fn(*a))
                ts.append(get_time() - t0)
            return float(np.median(ts))

        t_fwd = med(self._fwd_only, *args)
        t_grad = med(self._fwd_bwd, *args)
        t_step = med(
            self._train_step, self.params, self.opt_state, self.compute_graph,
            self.feature, self.label, self._train_mask01, key,
        )
        lines = [
            "DEBUGINFO:",
            f"#forward_time={t_fwd * 1000:.3f}(ms)",
            f"#backward_time={max(t_grad - t_fwd, 0.0) * 1000:.3f}(ms)",
            f"#update_time={max(t_step - t_grad, 0.0) * 1000:.3f}(ms)",
            f"#all_train_step_time={t_step * 1000:.3f}(ms)",
        ]
        return "\n".join(lines)

    def numerics_replay(self, epoch: int):
        """The non-finite provenance replay (obs/numerics): re-run the
        failing epoch's forward EAGERLY layer by layer through
        forward_taped — same inputs, same fold_in key — recording each
        layer's output and applying the chaos poison mid-layer
        (``poison_hook``). None when the model exposes no layer taps."""
        from neutronstarlite_tpu.obs import numerics

        if type(self).forward_taped is FullBatchTrainer.forward_taped:
            return None
        key = jax.random.fold_in(
            jax.random.PRNGKey(self.seed + 1), epoch
        )
        entries = []

        def tap(i, h):
            h = numerics.poison_hook(h, i)
            entries.append((i, "activation", f"acts/l{i}", h))
            return h

        logits = self.forward_taped(
            self.params, self.compute_graph, self.feature, key, tap
        )
        if logits is None:
            return None
        entries.append((None, "logits", "logits", logits))
        return entries

    def aot_args(self):
        """The exact argument tuple run() passes to the jitted train step —
        the uniform hook tools/aot_check uses to lower any registered model
        for an accelerator topology without executing it."""
        return (
            self.params, self.opt_state, self.compute_graph, self.feature,
            self.label, self._train_mask01, jax.random.PRNGKey(self.seed + 1),
        )

    def run(self) -> Dict[str, Any]:
        cfg = self.cfg
        self.open_run_root()
        with self.stage("run_begin"):
            key = jax.random.PRNGKey(self.seed + 1)
            log.info(
                "GNNmini::Engine[%s.%s] running [%d] Epochs",
                jax.default_backend(),
                type(self).__name__,
                cfg.epochs,
            )
            # bytes of logits brought to the host for an accuracy: the
            # counts below are made on the device, so none
            self.metrics.counter_add("acc.host_bytes", 0)
        with self.stage("ckpt_begin"):
            start_epoch = self.ckpt_begin()
        loss = None
        # NTS_PROFILE_DIR: emit a jax.profiler trace of the steady-state
        # epochs (from the 2nd epoch on, so compile noise stays out) — the
        # kernel-level truth behind the DEBUGINFO host timers
        from neutronstarlite_tpu.utils.profiling import maybe_trace

        trace_from = start_epoch + 1
        trace_cm = None
        # NTS_TRACE_STEP=1: two-program epochs (forward+backward, optim)
        # for real per-epoch stage spans; adds one host sync per epoch, so
        # it is opt-in. The fused path still attributes dispatch vs device
        # wait (the host-observable split of an async XLA step).
        split_step = os.environ.get("NTS_TRACE_STEP", "0") == "1"
        if split_step and self._train_step_stats is not None:
            # loud, not silent (the WIRE_DTYPE-off-ring lesson): the
            # split-epoch programs have no stats-fused variant, so a
            # user arming both knobs must know no tensor_stats will land
            log.warning(
                "NTS_TRACE_STEP=1 runs the split two-program epochs, "
                "which carry no fused numerics output — NTS_NUMERICS=1 "
                "emits NO tensor_stats this run (drop one of the two "
                "knobs)"
            )
        for epoch in range(start_epoch, cfg.epochs):
            if epoch == trace_from and epoch < cfg.epochs:
                trace_cm = maybe_trace(type(self).__name__)
                trace_cm.__enter__()
            with self.epoch_span(epoch):
                with self.stage("epoch_key", epoch):
                    ekey = jax.random.fold_in(key, epoch)
                stats_dev = None
                # per-epoch Train/Eval/Test accuracy from the training
                # forward's logits, the reference's oracle cadence
                # (Test(0/1/2) each epoch on X[last], GCN_CPU.hpp:241-248).
                # NOTE these cadence logits are TRAIN-mode (dropout active),
                # so mid-training Eval/Test lines are biased low relative to
                # the final eval-mode accuracies below — same bias as the
                # reference's cadence, kept for log parity.
                cadence = (
                    epoch % max(1, cfg.epochs // 20) == 0
                    or epoch == cfg.epochs - 1
                )
                counts_dev = None
                if split_step:
                    with self.stage("forward_backward", epoch) as s_fb:
                        loss, grads = self._fwd_bwd(
                            self.params, self.compute_graph, self.feature,
                            self.label, self._train_mask01, ekey,
                        )
                        jax.block_until_ready(loss)
                    with self.stage("optim", epoch) as s_opt:
                        self.params, self.opt_state = self._optim_step(
                            self.params, grads, self.opt_state
                        )
                        jax.block_until_ready(self.params)
                    # no logits: cadence accuracies are skipped this mode
                    t0 = s_fb.t0
                    stages = {
                        "forward_backward": s_fb.dur_s, "optim": s_opt.dur_s,
                    }
                else:
                    with self.stage("step_dispatch", epoch) as s_disp:
                        if self._train_step_stats is not None:
                            # NTS_NUMERICS=1: the stats-fused variant — same
                            # math, one extra all-scalar output (fetched
                            # every NTS_NUMERICS_EVERY epochs in
                            # maybe_emit_numerics)
                            (self.params, self.opt_state, loss, logits,
                             stats_dev) = self._train_step_stats(
                                self.params, self.opt_state,
                                self.compute_graph, self.feature, self.label,
                                self._train_mask01, ekey,
                            )
                        else:
                            self.params, self.opt_state, loss, logits = (
                                self._train_step(
                                    self.params, self.opt_state,
                                    self.compute_graph, self.feature,
                                    self.label, self._train_mask01, ekey,
                                )
                            )
                    if cadence:
                        # counted on the device, queued behind the step
                        # before the host waits for it: the chip goes from
                        # one to the other, and the logits stay where they are
                        with self.stage("accuracy_dispatch", epoch):
                            counts_dev = _split_counts(
                                logits, self.label, self.mask
                            )
                    with self.stage("step_device", epoch) as s_dev:
                        jax.block_until_ready(loss)
                    t0 = s_disp.t0
                    stages = {
                        "step_dispatch": s_disp.dur_s,
                        "step_device": s_dev.dur_s,
                    }
                with self.stage("loss_fetch", epoch):
                    self.maybe_emit_numerics(epoch, stats_dev)
                    # chaos hook (NTS_FAULT_SPEC): nan_loss/stall/crash fire
                    # here, before the loss reaches history, guards, or a
                    # checkpoint
                    loss = fault_point("epoch_loss", epoch=epoch, value=loss)
                    dt = get_time() - t0
                    self.epoch_times.append(dt)
                    self.loss_history.append(float(loss))
                with self.stage("epoch_emit", epoch):
                    self.emit_epoch(epoch, dt, loss, stages=stages)
                if counts_dev is not None:
                    with self.stage("host_accuracy", epoch):
                        self.report_split_counts(counts_dev, empty_lines=False)
                        self.metrics.counter_add("acc.device_counts")
                if cadence:
                    # the loss line must not depend on logits:
                    # NTS_TRACE_STEP=1 skips cadence accuracies but still
                    # has loss every epoch
                    log.info("Epoch %d loss %f", epoch, float(loss))
                with self.stage("ckpt_epoch_end", epoch):
                    self.ckpt_epoch_end(epoch)
        if trace_cm is not None:
            trace_cm.__exit__(None, None, None)
        with self.stage("ckpt_final"):
            self.ckpt_final()

        if os.environ.get("NTS_DEBUGINFO", "0") == "1":
            log.info("%s", self.debug_info(key))

        # benchmark mode (see ToolkitBase.skip_final_eval); the cadence
        # lines above already report train-mode accuracies
        if self.skip_final_eval(loss):
            accs = {"train": None, "eval": None, "test": None}
        else:
            with self.stage("final_eval"):
                with self.stage("eval_forward"):
                    logits_dev = self._eval_logits(
                        self.params, self.compute_graph, self.feature, key
                    )
                with self.stage("host_accuracy"):
                    accs = self.report_split_counts(
                        _split_counts(logits_dev, self.label, self.mask),
                        empty_lines=False,
                    )
        avg = self.avg_epoch_time()
        log.info(
            "--avg epoch time %.4f s (first %.2f s incl. compile)",
            avg,
            self.epoch_times[0] if self.epoch_times else 0.0,
        )
        # loss is None when a checkpoint restore resumed at/after cfg.epochs
        # (zero epochs ran): still report the restored model's accuracy
        result = {
            "loss": float(loss) if loss is not None else float("nan"),
            "acc": accs,
            "avg_epoch_s": avg,
        }
        self.finalize_metrics(result)
        return result
