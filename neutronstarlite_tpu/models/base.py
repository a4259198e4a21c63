"""Toolkit base: the init_graph / init_nn / run lifecycle every model follows.

Reference: each toolkit (toolkits/GCN_CPU.hpp etc.) implements
``init_graph()`` (build partitioned graph + context), ``init_nn()`` (read
hyperparams, load GNNDatum, create Parameters), and ``run()`` (epoch loop:
Forward, Test(0/1/2), Loss, backward, Update), registered by ALGORITHM string
in toolkits/main.cpp:53-187. This base class reproduces that lifecycle; the
device placement difference disappears (XLA runs on whatever jax.devices()
offers), so reference names like GCNCPU and GCN (GPU) map to the same
TPU implementation — the registry accepts all of them.
"""

from __future__ import annotations

import contextlib
import functools
import os
from typing import Callable, Dict, Iterator, Optional, Type

import jax
import jax.numpy as jnp
import numpy as np

from neutronstarlite_tpu import obs
from neutronstarlite_tpu.resilience import events as res_events
from neutronstarlite_tpu.resilience import guards as res_guards
from neutronstarlite_tpu.graph.dataset import GNNDatum
from neutronstarlite_tpu.graph.storage import CSCGraph, build_graph, load_edges
from neutronstarlite_tpu.ops.device_graph import DeviceGraph
from neutronstarlite_tpu.utils.config import InputInfo
from neutronstarlite_tpu.utils.logging import get_logger
from neutronstarlite_tpu.utils.timing import PhaseTimers

log = get_logger("models")

_REGISTRY: Dict[str, Type["ToolkitBase"]] = {}


def register_algorithm(*names: str):
    """Register a toolkit under its ALGORITHM string(s) (main.cpp:53-187)."""

    def deco(cls):
        for n in names:
            _REGISTRY[n.upper()] = cls
        return cls

    return deco


def get_algorithm(name: str) -> Type["ToolkitBase"]:
    try:
        return _REGISTRY[name.upper()]
    except KeyError:
        raise KeyError(
            f"unknown ALGORITHM {name!r}; known: {sorted(_REGISTRY)}"
        ) from None


@jax.jit
def _split_counts(logits_p, label_p, mask_p, valid_p=None):
    """[3] (correct, total) counts over mask splits 0/1/2, restricted to real
    (non-padding) vertices; ``valid_p`` None where every row is a vertex
    (the one-chip arrays). Inputs are vertex-space arrays (padded and
    sharded, or not); the sums reduce over the sharded axis inside jit.
    ``argmax`` takes the first largest, and a NaN as largest, as numpy's."""
    ok = jnp.argmax(logits_p, axis=-1) == label_p
    splits = jnp.arange(3, dtype=mask_p.dtype)
    sel = mask_p[None, :] == splits[:, None]  # [3, P*vp]
    if valid_p is not None:
        sel = sel & (valid_p > 0)[None, :]
    correct = jnp.sum(sel & ok[None, :], axis=1)
    total = jnp.sum(sel, axis=1)
    return correct, total


class ToolkitBase:
    """Shared lifecycle: graph + datum loading, accuracy reporting, timing."""

    # subclasses override: edge-weight mode for the aggregation operator
    weight_mode = "gcn_norm"

    def __init__(self, cfg: InputInfo, base_dir: Optional[str] = None, seed: int = 0):
        self.cfg = cfg
        self.base_dir = base_dir
        self.seed = seed
        self.timers = PhaseTimers()
        self.host_graph: Optional[CSCGraph] = None
        self.graph: Optional[DeviceGraph] = None
        self.datum: Optional[GNNDatum] = None
        self._raw_feature = None  # raw_feature's copy on a hoisting trainer
        self.epoch_times = []
        # per-epoch training losses, appended by every run loop — the
        # trajectory-equality oracle (two backends computing the same math
        # must produce the same CURVE, not just the same endpoint) reads
        # this; reference analog: the per-epoch loss lines GCN_CPU.hpp
        # prints each epoch
        self.loss_history: list = []
        # run-metrics registry (obs/): counters + the per-epoch JSONL
        # stream under NTS_METRICS_DIR; every run loop emits epoch events
        # and one consolidated run_summary via finalize_metrics()
        self.metrics = obs.open_run(
            cfg.algorithm or type(self).__name__, cfg=cfg, seed=seed
        )
        # span tracing (obs/trace): one trace per run. The root "run" span
        # opens here and closes in finalize_metrics; PhaseTimers buckets
        # and per-epoch spans parent under it, so the whole lifecycle
        # funnel (init_graph -> init_nn -> epochs -> finalize) reads as
        # one causal tree in tools/trace_timeline.
        self.tracer = obs.Tracer(self.metrics)
        self.timers.tracer = self.tracer
        self._run_span = None
        self.open_run_root()
        self._last_epoch_span = None
        self.run_summary_record: Optional[dict] = None
        # fault/recovery records from any layer (fault injection, guard
        # trips, checkpoint quarantine) land in this trainer's stream
        res_events.set_sink(self.metrics)
        # live telemetry plane (obs/): the SLO burn-rate engine evaluates
        # NTS_SLO_SPEC objectives (epoch_pNN_ms on trainers; serving arms
        # its own latency objectives) — ticked per epoch in emit_epoch —
        # and the opt-in scrape endpoint (NTS_METRICS_PORT) serves
        # /metrics, /healthz, /slo off this registry: a process-level
        # singleton that rebinds to the newest trainer (train-then-serve
        # runs hand the same stream to the serve stack, which rebinds)
        from neutronstarlite_tpu.obs import exporter as obs_exporter
        from neutronstarlite_tpu.obs.slo import SloEngine

        self.slo = SloEngine.from_env(self.metrics, scope="train")
        obs_exporter.maybe_start(self.metrics, slo=self.slo)

    # dist trainers build their own partitioned layout; the single-device
    # DeviceGraph upload would be O(E) wasted HBM for them
    needs_device_graph = True

    # trainers whose build_model honors KERNEL:fused_edge (the attention/
    # edge-op families: GAT / GGCN and their dist twins) set this True;
    # everywhere else the key refuses loudly (see _check_kernel)
    supports_fused_edge = False

    # trainers whose run loop honors SAMPLE_PIPELINE (the sampled family:
    # gcn_sample; serving reuses the same key through ServeOptions) set
    # this True; everywhere else an explicit mode refuses loudly — the
    # DIST_PATH refusal pattern (see _check_sample_pipeline)
    supports_sample_pipeline = False

    # ---- init_graph ------------------------------------------------------
    def _wants_ell(self) -> bool:
        """True when build_model will replace the DeviceGraph with ELL tables
        (OPTIM_KERNEL) — skip the O(E) device upload in that case."""
        return bool(
            self.cfg.optim_kernel and getattr(type(self), "supports_optim_kernel", False)
        )

    def _wants_fused_edge(self) -> bool:
        """True when build_model will route the edge chain through the
        fused blocked kernel (KERNEL:fused_edge, ops/fused_edge.py) —
        the DeviceGraph edge arrays are dead weight on that path too."""
        return bool(
            self.cfg.kernel == "fused_edge"
            and getattr(type(self), "supports_fused_edge", False)
        )

    def _build_device_graph(self) -> bool:
        return (
            type(self).needs_device_graph
            and not self._wants_ell()
            and not self._wants_fused_edge()
        )

    def init_graph(self) -> None:
        cfg = self.cfg
        edge_path = cfg.resolve_path(cfg.edge_file, self.base_dir)
        with self.timers.phase("graph_load"):
            if getattr(cfg, "undirected", False):
                # UNDIRECTED:1 — symmetrize at load
                # (load_undirected_from_directed, core/graph.hpp:640)
                from neutronstarlite_tpu.graph.storage import (
                    load_undirected_from_directed,
                )

                src, dst = load_undirected_from_directed(edge_path)
            else:
                src, dst = load_edges(edge_path)
            with self.timers.phase("host_graph_build"):
                self.host_graph = build_graph(
                    src, dst, cfg.vertices, weight=self.weight_mode
                )
            self._resolve_and_upload_graph()
        log.info(
            "loaded graph |V|=%d |E|=%d avg_deg=%.1f",
            self.host_graph.v_num,
            self.host_graph.e_num,
            self.host_graph.avg_degree,
        )

    # ---- init_nn ---------------------------------------------------------
    def init_nn(self) -> None:
        cfg = self.cfg
        sizes = cfg.layer_sizes()
        with self.timers.phase("datum_load"):
            mask_path = cfg.resolve_path(cfg.mask_file, self.base_dir)
            fmt = getattr(cfg, "data_format", "auto")
            use_ogb = fmt == "ogb" or (
                fmt == "auto" and bool(mask_path) and os.path.isdir(mask_path)
            )
            reader = (
                GNNDatum.read_feature_label_mask_ogb
                if use_ogb
                else GNNDatum.read_feature_label_mask
            )
            self.datum = reader(
                cfg.resolve_path(cfg.feature_file, self.base_dir),
                cfg.resolve_path(cfg.label_file, self.base_dir),
                mask_path,
                cfg.vertices,
                sizes[0],
                seed=self.seed,
            )
        self._finalize_datum()

    # trainers whose build_model honors the DIST_PATH selector (the
    # fuse-op dist family, models/gcn_dist.py) set this True; everywhere
    # else an explicit DIST_PATH must refuse loudly instead of silently
    # running a different exchange than the user is benchmarking
    supports_dist_path = False

    def _check_dist_path(self) -> None:
        cfg = self.cfg
        if getattr(type(self), "supports_dist_path", False):
            # mesh-vs-knob consistency for the family that CAN build a
            # 2D mesh (loud refusals: all_gather/mirror/OPTIM_KERNEL
            # cannot feature-shard; PARTITIONS must agree with Pv*Pf)
            from neutronstarlite_tpu.parallel.partitioner import (
                check_mesh_cfg,
            )

            check_mesh_cfg(cfg)
            return
        mesh = getattr(cfg, "mesh", "")
        if mesh not in ("", "auto"):
            raise ValueError(
                f"MESH:{mesh} is not available for ALGORITHM "
                f"{cfg.algorithm!r}: the 2D (vertex x feature) mesh "
                "partitioner (parallel/partitioner.py) serves the fuse-op "
                "dist family (GCNDIST / GINDIST / COMMNETDIST and their "
                "eager variants); other families have no feature-shardable "
                "exchange"
            )
        dist_path = getattr(cfg, "dist_path", "")
        if dist_path not in ("", "auto"):
            raise ValueError(
                f"DIST_PATH:{dist_path} is not available for ALGORITHM "
                f"{cfg.algorithm!r}: DIST_PATH selects the dense-feature "
                "dist aggregation path (all_gather family / ring_blocked) "
                "and serves the fuse-op dist family (GCNDIST / GINDIST / "
                "COMMNETDIST and their eager variants)"
            )
        if getattr(cfg, "wire_dtype", "") or os.environ.get("NTS_WIRE_DTYPE"):
            log.warning(
                "WIRE_DTYPE/NTS_WIRE_DTYPE only applies to "
                "DIST_PATH:ring_blocked on the fuse-op dist family; "
                "ALGORITHM %s ignores it", cfg.algorithm,
            )

    def _check_kernel(self) -> None:
        """Kernel-selection loudness at the lifecycle funnel (the PR 4
        DIST_PATH refusal pattern): a knob that would otherwise be
        silently ignored must refuse, not run a different kernel than the
        user is benchmarking."""
        cfg = self.cfg
        if cfg.pallas_kernel and not cfg.optim_kernel:
            raise ValueError(
                "PALLAS:1 requires OPTIM_KERNEL:1 — the Pallas block-sparse "
                "kernel is a layout of the OPTIM_KERNEL aggregation path "
                "and would be silently ignored without it; set "
                "OPTIM_KERNEL:1 (or drop PALLAS:1)"
            )
        if cfg.kernel == "fused_edge":
            if not getattr(type(self), "supports_fused_edge", False):
                raise ValueError(
                    f"KERNEL:fused_edge is not available for ALGORITHM "
                    f"{cfg.algorithm!r}: the fused SDDMM+softmax+SpMM kernel "
                    "serves the attention/edge-op families (GATCPU / GGCNCPU "
                    "and their dist twins GATDIST / GGCNDIST); other "
                    "families aggregate through OPTIM_KERNEL/PALLAS instead"
                )
            if cfg.optim_kernel or cfg.pallas_kernel:
                raise ValueError(
                    "KERNEL:fused_edge and OPTIM_KERNEL/PALLAS select "
                    "different kernel stacks for the same chain — choose "
                    "one (the fused kernel already subsumes the scatter-"
                    "free attention path)"
                )

    # trainers whose supervised path supports elastic degraded mode
    # (NTS_ELASTIC=1: rank-loss liveness detection + survivor replan,
    # resilience/elastic.py) — the fuse-op dist family (models/gcn_dist;
    # GIN/CommNet inherit). Everywhere else the switch refuses loudly at
    # the lifecycle funnel (the DIST_PATH refusal pattern): an elastic
    # knob that silently cannot replan would let a rank loss kill the
    # job the user armed elastic mode to survive.
    supports_elastic = False

    def _check_elastic(self) -> None:
        from neutronstarlite_tpu.resilience import elastic

        if not elastic.elastic_enabled():
            return
        if not getattr(type(self), "supports_elastic", False):
            raise ValueError(
                f"NTS_ELASTIC=1 is not available for ALGORITHM "
                f"{self.cfg.algorithm!r}: elastic degraded-mode training "
                "(rank-loss detection + survivor replan) serves the "
                "fuse-op dist family (GCNDIST / GINDIST / COMMNETDIST "
                "and their eager variants); single-chip and mirror-"
                "family trainers have no partitioned plan to rebuild"
            )

    def _check_sample_pipeline(self) -> None:
        """SAMPLE_PIPELINE loudness at the lifecycle funnel: a mode the
        run loop would silently ignore must refuse instead (the user is
        benchmarking a pipeline that never runs). Resolved through
        resolve_sample_pipeline so the NTS_SAMPLE_PIPELINE env override
        cannot bypass the refusal the cfg key gets."""
        cfg = self.cfg
        if getattr(type(self), "supports_sample_pipeline", False):
            return
        from neutronstarlite_tpu.sample.pipeline import (
            resolve_sample_pipeline,
        )

        mode = resolve_sample_pipeline(cfg)
        if mode != "sync":
            raise ValueError(
                f"SAMPLE_PIPELINE:{mode} is not available for ALGORITHM "
                f"{cfg.algorithm!r}: the async sampling pipeline serves "
                "the sampled mini-batch family (GCNSAMPLESINGLE) and the "
                "serve/ stack built on it; full-batch and dist trainers "
                "never sample"
            )

    def _resolve_tune_autos(self) -> None:
        """Auto-knob resolution (tune/select): DIST_PATH:auto /
        KERNEL:auto / ELL_LEVELS:auto / WIRE_DTYPE:auto resolve through
        the measured-decision cache (NTS_TUNE) into concrete cfg values.
        Called right after host_graph exists (init_graph / from_arrays)
        so the DeviceGraph upload decision sees the resolved kernel, and
        again — as a no-op — at the head of _finalize_datum for any
        construction path that skipped it. The funnel's validity checks
        always run AFTER resolution on the concrete values, so even a
        corrupt cache entry cannot smuggle in a combination the funnel
        refuses."""
        from neutronstarlite_tpu.tune import select as tune_select

        with self.timers.phase("tune_resolve"):
            tune_select.resolve_auto_knobs(self)

    def _resolve_and_upload_graph(self) -> None:
        """The funnel step both construction paths share once host_graph
        exists. Auto-knob resolution needs only host_graph + cfg, and the
        _wants_fused_edge/_wants_ell upload decision needs the RESOLVED
        kernel — resolving here (not in _finalize_datum, where it re-runs
        as a no-op) keeps KERNEL:auto from paying the O(E) DeviceGraph
        upload a pinned KERNEL:fused_edge skips."""
        self._resolve_tune_autos()
        if self._build_device_graph():
            with self.timers.phase("device_graph_upload"):
                self.graph = DeviceGraph.from_host(
                    self.host_graph, edge_chunk=self.cfg.edge_chunk or None
                )

    def _finalize_datum(self) -> None:
        self._resolve_tune_autos()
        self._check_kernel()
        self._check_dist_path()
        self._check_sample_pipeline()
        self._check_elastic()
        self.build_model()

    # ---- the once-aggregated input ---------------------------------------
    # True once build_model has put the aggregated feature table in the
    # place of the features (``feature`` / ``feature_p``); see
    # hoists_input_aggregate
    input_hoisted = False

    def hoists_input_aggregate(self) -> bool:
        """Whether layer 0 of this trainer's forward reads the datum
        through the aggregation ALONE (the standard-order GCN: aggregate ->
        bn -> dense). Nothing trained and nothing random stands before
        that aggregation, so it is the same array in every epoch: the
        funnel computes it once (the ``input_aggregate`` phase) and the
        step takes it as its feature argument. A property of the model's
        forward, answered by the class that owns the forward; the default
        is no, and a subclass that changes the order or what layer 0 reads
        must not inherit a yes (models/gcn.py, models/gcn_dist.py)."""
        return False

    def record_table_stats(self, stats: dict) -> None:
        """How far the aggregation's level tables are padded, where a
        reader finds it: gauges ``agg.slots_per_edge`` (slots of both
        directions over twice the edges; stacked tables count every
        device's slots, so the ratio is one device's) and ``agg.levels``,
        and the same counts on a ``tables_stats`` phase span. ``stats`` is
        a table pair's ``padding_stats``."""
        slots = int(stats["fwd_slots"] + stats["bwd_slots"])
        edges = 2 * int(stats["real_edges"])
        levels = int(stats["levels"])
        self.metrics.gauge_set("agg.slots_per_edge", slots / max(edges, 1))
        self.metrics.gauge_set("agg.levels", levels)
        with self.timers.phase(
            "tables_stats", slots=slots, edges=edges, levels=levels
        ):
            pass

    def host_input_features(self) -> np.ndarray:
        """The datum's features as a hoisting trainer uploads them: already
        in the compute dtype, so that under PRECISION:bfloat16 the float32
        table never lands on the device. numpy's cast (ml_dtypes) and XLA's
        both round to nearest even, so the device's cast of the float32
        table gives the same bits (pinned in tests/test_input_hoist.py)."""
        feat = self.datum.feature
        if self.cfg.precision == "bfloat16":
            return feat.astype(jnp.bfloat16)
        return feat

    # Single-device copies of the datum, uploaded on first use. The
    # full-batch and sampled trainers (and the serve/stream stacks on top of
    # them) read these; the dist trainers place their own sharded arrays and
    # never do, so no whole [V, f] copy lands on device 0 beside the shards.
    # On a trainer that hoists the input aggregate, ``feature`` is the
    # step's feature argument, the aggregated table, set by build_model.
    @functools.cached_property
    def feature(self) -> jax.Array:
        with self.timers.phase("datum_upload"):
            return jnp.asarray(self.datum.feature)

    @property
    def raw_feature(self) -> jax.Array:
        """The raw feature rows on the device, for whoever gathers rows of
        them beside a trainer (serve/, stream/): ``feature`` itself, except
        where that is the aggregated table; then a copy of the datum's,
        uploaded on first use."""
        if not self.input_hoisted:
            return self.feature
        if self._raw_feature is None:
            with self.timers.phase("datum_upload"):
                self._raw_feature = jnp.asarray(self.datum.feature)
        return self._raw_feature

    @raw_feature.setter
    def raw_feature(self, value: jax.Array) -> None:
        if self.input_hoisted:
            self._raw_feature = value
        else:
            self.feature = value

    @functools.cached_property
    def label(self) -> jax.Array:
        with self.timers.phase("datum_upload"):
            return jnp.asarray(self.datum.label.astype(np.int32))

    @functools.cached_property
    def mask(self) -> jax.Array:
        with self.timers.phase("datum_upload"):
            return jnp.asarray(self.datum.mask)

    @classmethod
    def from_arrays(
        cls,
        cfg: InputInfo,
        src: np.ndarray,
        dst: np.ndarray,
        datum: GNNDatum,
        seed: int = 0,
        host_graph=None,
    ) -> "ToolkitBase":
        """Construct directly from in-memory edge list + datum (tests/bench).

        ``host_graph``: pass a prebuilt CSCGraph (matching ``weight_mode``)
        to share one host build across many trainers — the bench sweep
        rebuilds 9 configs over the same 114M-edge graph and the host
        CSC/CSR build dominates its wall time otherwise."""
        t = cls(cfg, seed=seed)
        if host_graph is None:
            with t.timers.phase("host_graph_build"):
                host_graph = build_graph(
                    src, dst, cfg.vertices, weight=cls.weight_mode
                )
        t.host_graph = host_graph
        t._resolve_and_upload_graph()
        t.datum = datum
        t._finalize_datum()
        return t

    def build_model(self) -> None:
        raise NotImplementedError

    # ---- dist-trainer mesh resolution ------------------------------------
    simulate: Optional[bool] = None  # None -> read NTS_DIST_SIMULATE

    def resolve_simulate(self) -> bool:
        """ONE resolution of the sim-twin switch (class attr pin or
        NTS_DIST_SIMULATE=1), shared by resolve_mesh and the 2D
        partitioner branch so the env read can never drift between the
        1D and mesh paths."""
        if self.simulate is None:
            self.simulate = os.environ.get("NTS_DIST_SIMULATE", "0") == "1"
        return self.simulate

    def resolve_mesh(self):
        """(mesh, partitions) for dist trainers. ``simulate`` (class attr or
        NTS_DIST_SIMULATE=1) selects the collective-free sim ops with
        ``mesh=None`` — the single-core test rig; otherwise a real mesh over
        PARTITIONS (or all) devices."""
        from neutronstarlite_tpu.parallel.mesh import make_mesh

        if self.resolve_simulate():
            return None, (self.cfg.partitions or 2)
        mesh = make_mesh(self.cfg.partitions or None)
        return mesh, mesh.devices.size

    # ---- checkpoint / resume (SURVEY.md section 5 gap-fill) --------------
    # params/opt_state live on every trainer (replicated on dist meshes, so
    # a host-side pytree save works everywhere)
    def checkpoint_state(self):
        return {"params": self.params, "opt": self.opt_state}

    def _ckpt_backend(self) -> str:
        # resolve_backend also degrades gracefully: orbax requested on a
        # machine without orbax installed warns and falls back to npz
        # instead of dying on a bare ImportError mid-run
        from neutronstarlite_tpu.utils.checkpoint import resolve_backend

        return resolve_backend(self.cfg.ckpt_backend)

    def save(self, path: str, epoch: int) -> None:
        from neutronstarlite_tpu.utils.checkpoint import save_checkpoint

        backend = self._ckpt_backend()
        if backend == "orbax":
            # async + sharded: EVERY process participates (orbax
            # coordinates the distributed write; dir is shared storage)
            save_checkpoint(path, self.checkpoint_state(), epoch,
                            backend="orbax")
            return
        # npz: params are replicated, one writer suffices, and concurrent
        # writers on a shared checkpoint dir would race on the tmp file
        if jax.process_index() != 0:
            return
        # the resolved backend is passed explicitly: an env-level
        # NTS_CKPT_BACKEND=orbax must not override a cfg-level npz opt-out
        # at the lower layer
        save_checkpoint(path, self.checkpoint_state(), epoch, backend=backend)

    @staticmethod
    def _restore_like(template, arr):
        """Put a restored host array back with the template leaf's sharding
        (dist params are NamedSharding-replicated over the global mesh; a
        bare jnp.asarray would be process-local and break the next step)."""
        a = jnp.asarray(arr)
        sh = getattr(template, "sharding", None)
        return jax.device_put(a, sh) if sh is not None else a

    def _validate_restored(self, state) -> None:
        """Reject a checkpoint whose leaf shapes no longer match the model
        (e.g. HIDDEN changed between save and resume) BEFORE the tree.map
        — the raw failure is an opaque broadcast error deep inside
        device_put; this one names the offending keys."""
        mismatches = []
        for name, template in (("params", self.params), ("opt", self.opt_state)):
            got = state.get(name)
            if got is None:
                continue
            t_leaves = jax.tree_util.tree_flatten_with_path(template)[0]
            g_leaves = jax.tree_util.tree_flatten(got)[0]
            for (path, t_leaf), g_leaf in zip(t_leaves, g_leaves):
                t_shape = tuple(np.shape(t_leaf))
                g_shape = tuple(np.shape(g_leaf))
                if t_shape != g_shape:
                    mismatches.append(
                        f"{name}{jax.tree_util.keystr(path)}: "
                        f"checkpoint {g_shape} vs model {t_shape}"
                    )
        if mismatches:
            raise ValueError(
                "checkpoint does not fit this model (did LAYERS/HIDDEN "
                "change between save and resume?); mismatched leaves: "
                + "; ".join(mismatches)
            )

    def _apply_restored(self, state) -> None:
        self._validate_restored(state)
        self.params = jax.tree.map(self._restore_like, self.params, state["params"])
        self.opt_state = jax.tree.map(self._restore_like, self.opt_state, state["opt"])

    def restore(self, path: str) -> int:
        """Returns the epoch to resume from (0 when no checkpoint exists)."""
        from neutronstarlite_tpu.utils.checkpoint import restore_checkpoint

        got = restore_checkpoint(
            path, self.checkpoint_state(), backend=self._ckpt_backend()
        )
        if got is None:
            return 0
        state, step = got
        self._apply_restored(state)
        log.info("restored checkpoint at epoch %d from %s", step, path)
        return step

    def ckpt_begin(self) -> int:
        """Resume epoch for the run loop (0 without CHECKPOINT_DIR); a
        mid-run resume is recorded as a ``recovery(action=resume)`` obs
        event — the successor process of a crash/preemption announcing it
        picked the run back up — except during an in-process supervised
        retry, whose rollback the supervisor already recorded.

        A supervised retry also rewinds epoch_times/loss_history to the
        resume point: they describe the LOGICAL training trajectory, and
        the rolled-back attempt's tail (including the poisoned epoch)
        must not double-count in run_summary's epoch aggregates. Registry
        counters and timing histograms are deliberately NOT rewound —
        they measure PHYSICAL work done (bytes actually shipped, epochs
        actually executed, replays included); the
        ``resilience.replayed_epochs`` counter records the gap so the two
        views reconcile. The per-epoch JSONL stream keeps the full
        history either way.

        If the supervisor chose rollback but every retained checkpoint
        failed verification (restore quarantined them all and returned
        nothing), re-entering with the poisoned in-memory state would
        burn every restart on the same fault — rebuild the model from
        scratch instead."""
        retry = getattr(self, "_supervised_retry", False)
        start = self._ckpt_resume()
        if retry:
            if start == 0 and retry == "rollback":
                log.warning(
                    "supervised rollback found no restorable checkpoint "
                    "under %s; rebuilding the model from scratch",
                    self.cfg.checkpoint_dir,
                )
                self.build_model()
                res_events.emit_recovery(action="restart", epoch=0)
            first = getattr(self, "_first_epoch_trained", None)
            keep = max(start - (first if first is not None else 0), 0)
            replayed = len(self.epoch_times) - keep
            if replayed > 0:
                self.metrics.counter_add(
                    "resilience.replayed_epochs", replayed
                )
            del self.epoch_times[keep:]
            del self.loss_history[keep:]
            if keep == 0:
                # lists emptied (restart, or a fallback below the
                # anchor): the next trained epoch re-anchors the mapping
                self._first_epoch_trained = None
        elif start > 0:
            res_events.emit_recovery(action="resume", epoch=start)
        self._supervised_retry = False
        return start

    def _ckpt_resume(self) -> int:
        """Resume epoch for the run loop (0 without CHECKPOINT_DIR).

        Multi-host: only process 0 writes checkpoints (save()), and
        CHECKPOINT_DIR may not be shared storage — so the restored state and
        resume epoch are broadcast from process 0. Otherwise non-zero
        processes would restart at epoch 0 with fresh params while process 0
        resumes at N, desynchronizing the collective counts (the reference
        sidesteps this because every MPI rank reads its own dump file,
        core/graph.hpp:528-583)."""
        if not self.cfg.checkpoint_dir:
            return 0
        backend = self._ckpt_backend()
        if jax.process_count() <= 1:
            return self.restore(self.cfg.checkpoint_dir)
        if backend == "orbax":
            from neutronstarlite_tpu.utils.checkpoint import orbax_latest_step

            if orbax_latest_step(self.cfg.checkpoint_dir) is not None:
                # orbax multi-host: the restore itself is symmetric —
                # every process calls it and arrays land on their
                # shardings from shared storage; no broadcast staging
                return self.restore(self.cfg.checkpoint_dir)
            # orbax requested but no COMPLETED orbax step exists (backend
            # switched mid-run, or a first async save was interrupted —
            # the subdir may exist yet be empty, ADVICE r4): npz dirs may
            # be process-0-local, so the restore MUST go through the
            # broadcast path below — a symmetric per-rank npz read would
            # desynchronize resume epochs

        # Multi-process: keep every step SYMMETRIC across ranks. A naive
        # per-rank restore deadlocks — device_put onto a multi-process
        # sharding runs an internal value-equality allgather, and a rank
        # whose dir is empty never joins it. So: (1) host-side file read
        # only, (2) broadcast host state from process 0, (3) identical
        # device_puts everywhere.
        from jax.experimental import multihost_utils

        from neutronstarlite_tpu.utils.checkpoint import restore_checkpoint

        got = restore_checkpoint(
            self.cfg.checkpoint_dir, self.checkpoint_state(), backend="npz"
        )
        step = int(multihost_utils.broadcast_one_to_all(np.int32(got[1] if got else 0)))
        if step == 0:  # no checkpoint anywhere: skip the model-sized broadcast
            return 0
        if got is not None:
            host_state = jax.tree.map(np.asarray, got[0])
        else:  # same pytree structure as a restored state, current values
            host_state = jax.tree.map(np.asarray, self.checkpoint_state())
        host_state = multihost_utils.broadcast_one_to_all(host_state)
        self._apply_restored(host_state)
        log.info("restored checkpoint at epoch %d (broadcast from process 0)", step)
        return step

    def ckpt_epoch_end(self, epoch: int) -> None:
        cfg = self.cfg
        if (
            cfg.checkpoint_dir
            and cfg.checkpoint_every > 0
            and (epoch + 1) % cfg.checkpoint_every == 0
        ):
            self.save(cfg.checkpoint_dir, epoch + 1)

    def ckpt_final(self) -> None:
        if self.cfg.checkpoint_dir:
            self.save(self.cfg.checkpoint_dir, self.cfg.epochs)
            from neutronstarlite_tpu.utils.checkpoint import (
                finalize_checkpoints,
            )

            finalize_checkpoints()  # drain async orbax writes (npz: no-op)

    # ---- accuracy / loss helpers ----------------------------------------
    @staticmethod
    def masked_nll_loss(logits: jax.Array, label: jax.Array, mask01: jax.Array):
        """nll_loss on masked log_softmax (GCN_CPU.hpp:187-196)."""
        logp = jax.nn.log_softmax(logits, axis=-1)
        picked = jnp.take_along_axis(logp, label[:, None], axis=-1)[:, 0]
        denom = jnp.maximum(mask01.sum(), 1.0)
        return -(picked * mask01).sum() / denom

    def report_split_counts(self, counts, empty_lines: bool = True):
        """Fetch ``_split_counts``' (correct[3], total[3]) from the device
        (six integers: the only thing an accuracy brings to the host), log
        the Train / Eval / Test lines (Test(0/1/2), GCN_CPU.hpp:142-171) and
        return ``{"train": acc, "eval": acc, "test": acc}``. A split with no
        vertex reads 0.0; the one-chip loop writes no line for it
        (``empty_lines=False``), the sharded report does."""
        correct, total = jax.device_get(counts)
        accs = {}
        for which, nm in enumerate(("Train", "Eval", "Test")):
            n, c = int(total[which]), int(correct[which])
            acc = c / n if n else 0.0
            if n or empty_lines:
                log.info("%s Acc: %f %d %d", nm, acc, n, c)
            accs[nm.lower()] = acc
        return accs

    def dist_eval_report(self, logits_p, label_p, mask_p, valid_p):
        """Accuracy for the sharded trainers: per-split (correct, total)
        counters reduced INSIDE jit over the sharded vertex axis — XLA inserts
        the cross-device (and cross-host) all-reduce, the TPU form of the
        reference's MPI_Allreduce on accuracy counters
        (toolkits/GCN_CPU.hpp:157-158). Never materializes global logits on
        the host, so it is multi-process safe where a
        ``np.asarray(global_sharded_logits)`` gather is not."""
        return self.report_split_counts(
            _split_counts(logits_p, label_p, mask_p, valid_p)
        )

    def avg_epoch_time(self) -> float:
        """Mean epoch time, excluding the first (compile) epoch when more
        than one was timed — a 1-epoch run reports its single epoch rather
        than a fictitious 0.0."""
        times = self.epoch_times[1:] if len(self.epoch_times) > 1 else self.epoch_times
        return float(np.mean(times)) if times else 0.0

    @staticmethod
    def skip_final_eval(loss) -> bool:
        """NTS_FINAL_EVAL=0: benchmark mode — the end-of-run eval-mode
        forward is a SECOND full-scale program compile, pure overhead for
        an epoch-time measurement (and a failure surface: a dying compile
        service mid-eval once sank a whole bench sweep). Only skippable
        when training actually ran (loss is not None) so a restore-only
        run still reports the restored model's accuracy."""
        return os.environ.get("NTS_FINAL_EVAL", "1") == "0" and loss is not None

    # ---- live spans of the run loops -------------------------------------
    # One vocabulary for every run loop (docs/OBSERVABILITY.md, Tracing):
    # the ``epoch`` span covers the whole iteration, its ``stage`` children
    # are opened around the work itself, and each is a TraceAnnotation in
    # any active profiler session. No span adds a device sync.
    def open_run_root(self) -> None:
        """Open the ``run`` root span unless one is open: at construction,
        and again at the top of a ``run()`` that follows a finished one
        (``finalize_metrics`` closed the root; a warm-up ``run()`` then a
        measured one on the same trainer), so every epoch has a parent."""
        if self._run_span is None:
            self._run_span = self.tracer.begin(
                "run", cat="lifecycle",
                algorithm=self.cfg.algorithm or type(self).__name__,
            )

    def _close_run_root(self) -> None:
        if self._run_span is not None:
            self.tracer.end(self._run_span, epochs=len(self.epoch_times))
            self._run_span = None

    @contextlib.contextmanager
    def epoch_span(self, epoch: int) -> Iterator["obs.trace.SpanHandle"]:
        """The live ``epoch`` span: a run loop opens it at the top of the
        iteration and leaves it after ``ckpt_epoch_end``. Its ``t0`` is
        the epoch's start on the ``get_time`` clock."""
        with self.tracer.span(
            "epoch", cat="epoch", parent=self._run_span, epoch=int(epoch)
        ) as span:
            # NTS_TRACE=0 still hands out a handle (ids allocate, nothing
            # is emitted) — a disabled tracer must not leak phantom span
            # ids into ring_step records' epoch_span join field
            self._last_epoch_span = span if self.tracer.enabled else None
            yield span

    def stage(self, name: str, epoch: Optional[int] = None):
        """A live ``cat="stage"`` span under the innermost span this thread
        has open: the ``epoch`` span inside an iteration (pass ``epoch``),
        ``run`` (or an enclosing stage) around the loop. The handle's
        ``dur_s`` is set when the ``with`` block ends."""
        attrs = {} if epoch is None else {"epoch": int(epoch)}
        return self.tracer.span(
            name, cat="stage",
            parent=self.tracer.current() or self._run_span, **attrs,
        )

    # ---- run metrics -----------------------------------------------------
    def emit_epoch(self, epoch: int, seconds: float, loss=None,
                   stages: Optional[dict] = None, **extra):
        """Record one trained epoch in the metrics stream (run loops call
        this right after appending to epoch_times/loss_history), then run
        the per-epoch health guards (resilience/guards) — every run loop
        funnels through here, so a guard trip always happens AFTER the
        faulty epoch is visible in the stream and BEFORE ckpt_epoch_end
        could persist a poisoned checkpoint. Guards only raise when armed
        (supervised_run / NTS_GUARDS=1).

        ``stages``: ordered {name: seconds} sub-intervals of this epoch
        (``step_dispatch``/``step_device``, or the NTS_TRACE_STEP split's
        ``forward_backward``/``optim``), the durations of the loop's live
        stage spans of those names — attached to the epoch event for flat
        consumers. The spans themselves (``epoch`` and its stages) are the
        run loop's, opened where the work happens (``epoch_span`` /
        ``stage``): nothing is reconstructed here."""
        if getattr(self, "_first_epoch_trained", None) is None:
            # anchor for mapping epoch numbers onto epoch_times indices
            # (a crash-resumed trainer's first trained epoch is not 0)
            self._first_epoch_trained = epoch
        if stages:
            extra = dict(extra, stages={
                k: float(v) for k, v in stages.items()
            })
        rec = self.metrics.epoch_event(
            epoch, seconds,
            loss=float(loss) if loss is not None else None, **extra,
        )
        # step-time distribution (obs/hist): epoch quantiles that survive
        # rotation and merge across ranks — the scalar epoch timing stat
        # only carries min/max/avg
        self.metrics.hist_observe("train.epoch_ms", seconds * 1000.0)
        if self.slo is not None:
            # epoch objectives (epoch_pNN_ms) evaluate once per epoch; a
            # breach emits slo_status and snapshots the flight recorder
            self.slo.tick()
        res_guards.epoch_check(self, epoch, seconds, loss)
        return rec

    # ---- numerics plane (obs/numerics) -----------------------------------
    # Trainers that fuse the tensor-stat tree-reduce into their step
    # program (NTS_NUMERICS=1) hand the step's stats output here each
    # epoch; the host fetch — the only per-epoch cost — happens every
    # NTS_NUMERICS_EVERY epochs. Called BEFORE emit_epoch so a failing
    # epoch's stats are in the stream before its guard trips.
    def maybe_emit_numerics(self, epoch: int, stats_dev) -> None:
        if stats_dev is None:
            return
        from neutronstarlite_tpu.obs import numerics

        if epoch % numerics.numerics_every() != 0:
            return
        try:
            numerics.emit_stats(self.metrics, jax.device_get(stats_dev),
                                epoch)
        except Exception as e:  # telemetry must never kill a run
            log.warning("numerics emission failed at epoch %d: %s",
                        epoch, e)

    def numerics_replay(self, epoch: int):
        """Ordered ``(layer, op, label, array)`` eager intermediates of
        the failing step's forward, for the non-finite provenance
        bisection (obs/numerics.capture_provenance). None = this trainer
        has no replay hook; provenance degrades to an unattributed
        record. Implementations apply ``numerics.poison_hook`` inside
        the forward so the ``nan_loss@layer=k`` chaos poison lands
        mid-layer."""
        return None

    def record_epoch_wire(self, epoch: int, seconds: float, loss,
                          bytes_fwd: int, exchanges: int, **extra):
        """Epoch event + live wire counters in one step — the shared tail
        of every dist trainer's epoch loop, so the counter names and the
        event fields can never drift between trainers."""
        self.metrics.counter_add("wire.bytes_fwd", bytes_fwd)
        self.metrics.counter_add("wire.exchanges", exchanges)
        return self.emit_epoch(
            epoch, seconds, loss, wire_bytes_fwd=bytes_fwd, **extra
        )

    def finalize_metrics(self, result: Optional[dict] = None) -> dict:
        """Emit the consolidated run_summary record (idempotent: a second
        call returns the first record). Aggregates epoch timings, the
        first epoch beside the warm ones, the compiler's counters
        (obs/compiles), phase buckets, the counter/
        gauge snapshot (wire volume), device memory, and the final result.
        """
        if self.run_summary_record is not None:
            self._close_run_root()  # a later run() reopened it
            return self.run_summary_record
        from neutronstarlite_tpu.obs import collectors
        from neutronstarlite_tpu.tools.drift_audit import audit_registry
        from neutronstarlite_tpu.utils.platform import device_facts

        with self.stage("finalize_metrics"):
            if self.slo is not None:
                self.slo.close()  # final forced evaluation -> last slo_status
            fields: dict = {
                "device": device_facts(),
                "epochs": len(self.epoch_times),
                "epoch_time": collectors.steady_state_stats(self.epoch_times),
                "avg_epoch_s": self.avg_epoch_time(),
                "epoch_times_s": [float(t) for t in self.epoch_times],
                "loss_history": [float(v) for v in self.loss_history],
                "phases": collectors.phase_snapshot(self.timers),
                "memory": collectors.device_memory_stats(),
                "compile_cache": collectors.compile_cache_info(self.metrics),
            }
            if result is not None:
                fields["result"] = {
                    "loss": result.get("loss"),
                    "acc": result.get("acc"),
                    "avg_epoch_s": result.get("avg_epoch_s"),
                }
            # prediction-drift audit (tools/drift_audit): the analytic wire
            # pricing vs the live counters, emitted as typed model_drift
            # records BEFORE the summary so a drifted run's stream carries
            # the verdict (NTS_DRIFT_AUDIT=0 disables; never raises)
            audit_registry(self.metrics, len(self.epoch_times))
        # close the root lifecycle span BEFORE the summary so the span is
        # part of the stream the summary consolidates
        self._close_run_root()
        self.run_summary_record = self.metrics.run_summary(**fields)
        self._append_ledger_row()
        self.metrics.close()
        return self.run_summary_record

    def _ledger_graph_digest(self) -> Optional[str]:
        """The canonical graph digest for the perf-ledger row key —
        reuses the tuner's cached digest when one exists; computed once
        otherwise (only when the ledger is armed: the lexsort is O(E))."""
        digest = getattr(self, "_tune_graph_digest", None)
        if digest is not None or self.host_graph is None:
            return digest
        try:
            from neutronstarlite_tpu.graph.digest import graph_digest

            digest = graph_digest(self.host_graph)
            self._tune_graph_digest = digest
            return digest
        except Exception as e:
            log.warning("ledger graph digest unavailable: %s", e)
            return None

    def _append_ledger_row(self) -> None:
        """One kind=run row into the cross-run perf ledger
        (obs/ledger.py, NTS_LEDGER_DIR; disabled = no-op, failure =
        warning — the ledger never fails a run)."""
        from neutronstarlite_tpu.obs import ledger as obs_ledger

        if not obs_ledger.ledger_dir():
            return
        try:
            obs_ledger.append_row(obs_ledger.run_row(
                self.run_summary_record, self._ledger_graph_digest(),
            ))
        except Exception as e:
            log.warning("perf ledger append failed: %s", e)

    # ---- run -------------------------------------------------------------
    def run(self):
        raise NotImplementedError

    def report(self) -> str:
        return self.timers.report()
