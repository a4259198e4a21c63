"""Flat KEY:VALUE config system, compatible with the reference .cfg format.

Reference: ``InputInfo::readFromCfgFile`` (core/GraphSegment.cpp:222-292) parses
a flat file of ``KEY:VALUE`` lines; ``Graph::init_gnnctx[_fanout]``
(core/graph.hpp:293-336) parses the dash-separated LAYERS / FANOUT strings;
``RuntimeInfo`` (core/GraphSegment.h:148) carries the per-run execution flags.

This module keeps the exact same on-disk format (the reference's shipped
``gcn_cora.cfg`` etc. parse unchanged) but the runtime flags map to TPU
concepts: PROC_CUDA becomes a generic "accelerate" switch, PROC_OVERLAP keeps
its meaning (overlap ring communication with aggregation), and partitioning is
taken from the JAX mesh rather than an MPI world size.
"""

from __future__ import annotations

import dataclasses
import os
from typing import List, Optional


@dataclasses.dataclass
class GNNContext:
    """Layer-size / fan-out metadata (reference: GNNContext, GraphSegment.h:175)."""

    layer_size: List[int] = dataclasses.field(default_factory=list)
    fanout: List[int] = dataclasses.field(default_factory=list)
    label_num: int = 0
    p_id: int = 0
    p_v_s: int = 0
    p_v_e: int = 0

    @property
    def max_layer(self) -> int:
        return len(self.layer_size) - 1


@dataclasses.dataclass
class RuntimeInfo:
    """Execution flags (reference: RuntimeInfo, GraphSegment.h:148-174)."""

    process_local: bool = False
    process_overlap: bool = False
    with_weight: bool = True
    with_cuda: bool = False  # kept for cfg compat; on TPU: "use accelerator"
    process_rep: bool = False
    reduce_comm: bool = False
    copy_data: bool = False
    lock_free: bool = False
    optim_kernel_enable: bool = False
    epoch: int = -1
    curr_layer: int = -1
    embedding_size: int = -1


_INT_KEYS = {"VERTICES", "EPOCHS", "BATCH_SIZE", "DECAY_EPOCH"}
_FLOAT_KEYS = {"LEARN_RATE", "WEIGHT_DECAY", "DECAY_RATE", "DROP_RATE"}
_BOOL_KEYS = {
    "PROC_OVERLAP",
    "PROC_LOCAL",
    "PROC_CUDA",
    "PROC_REP",
    "LOCK_FREE",
    "OPTIM_KERNEL",
}
_STR_KEYS = {
    "ALGORITHM",
    "EDGE_FILE",
    "FEATURE_FILE",
    "LABEL_FILE",
    "MASK_FILE",
    "LAYERS",
    "FANOUT",
}


@dataclasses.dataclass
class InputInfo:
    """Parsed config (reference: InputInfo, GraphSegment.h:186-220)."""

    algorithm: str = ""
    vertices: int = 0
    epochs: int = 10
    batch_size: int = 64
    layer_string: str = ""
    fanout_string: str = ""
    edge_file: str = ""
    feature_file: str = ""
    label_file: str = ""
    mask_file: str = ""
    learn_rate: float = 0.01
    weight_decay: float = 0.0001
    decay_rate: float = 0.97
    decay_epoch: int = 100
    drop_rate: float = 0.5
    process_overlap: bool = False
    process_local: bool = False
    with_cuda: bool = False
    process_rep: bool = False
    lock_free: bool = False
    optim_kernel: bool = False
    # nts-tpu extensions (default values keep reference cfgs parsing unchanged)
    partitions: int = 0  # 0 = use all devices in the mesh
    precision: str = "float32"  # or "bfloat16" for the aggregation path
    checkpoint_dir: str = ""  # enable checkpoint/resume when set
    checkpoint_every: int = 0  # epochs between checkpoints (0 = end only)
    ckpt_backend: str = ""  # "" -> NTS_CKPT_BACKEND env / npz; "orbax" =
    # async + sharded saves (utils/checkpoint.py; dir must be shared
    # storage on multi-host)
    # DepCache hybrid dependency management (parallel/feature_cache.py;
    # reference replication_threshold graph.hpp:179, FeatureCache
    # NtsScheduler.hpp:556). Active when PROC_REP:1.
    rep_threshold: int = 0  # out-degree >= threshold => replicate/cache row;
    # -1 (REP_THRESHOLD:auto) = choose under the CACHE_BUDGET_MIB budget
    cache_budget_mib: int = 256  # HBM budget/device for the replicated rows
    cache_refresh: int = 1  # epochs between deep-layer cache refreshes
    sublinear: bool = False  # activation recomputation (ntsSubLinearNNOP)
    undirected: bool = False  # UNDIRECTED:1 -> symmetrize the edge list at
    # load (both directions of every stored edge), the reference's
    # load_undirected_from_directed (core/graph.hpp:640)
    data_format: str = "auto"  # DATA_FORMAT: nts (ID-prefixed text tables,
    # readFeature_Label_Mask) | ogb (CSV features, bare labels, mask DIR of
    # train/valid/test.csv — readFeature_Label_Mask_OGB,
    # core/ntsDataloador.hpp:223) | auto (ogb iff MASK_FILE is a directory)
    comm_layer: str = "auto"  # dist aggregation exchange: ring (dense
    # ppermute rotation), ell (all_gather + gather-only ELL, the OPTIM_KERNEL
    # path), mirror (compacted active-mirror all_to_all — the analog of the
    # reference's active-only messages, comm/network.cpp:505-518), or auto
    # (pick mirror vs ring by estimated wire rows; OPTIM_KERNEL:1 -> ell)
    dist_path: str = ""  # dist aggregation path override, one level above
    # COMM_LAYER: "" / auto (keep the COMM_LAYER selection), all_gather
    # (force the gather-only OPTIM_KERNEL family), ring_blocked (the
    # ring-pipelined blocked exchange, parallel/dist_ring_blocked.py —
    # O(2*vp) exchange memory, comm/compute overlap), ring_blocked_sim
    # (its collective-free twin, single-core CI parity)
    mesh: str = ""  # MESH: 2D (vertex x feature) device-mesh shape for the
    # fuse-op dist family (parallel/partitioner.py): "" (legacy 1D vertex
    # sharding), "Pv,Pf" (also accepts "PvxPf"; Pv vertex partitions, each
    # feature slab split Pf ways — per-device feature memory O(vp*f/Pf)),
    # or auto (the tune/ autotuner picks the shape from the factorizations
    # of PARTITIONS). Env override NTS_MESH (launcher parity), folded in at
    # the lifecycle funnel so it cannot bypass the validity checks.
    wire_dtype: str = ""  # ICI exchange dtype for the ring-pipelined path:
    # "" / f32 / float32 (ship the compute dtype) or bf16 / bfloat16
    # (halve wire bytes; the per-step accumulator stays f32), or auto (let
    # the tune/ autotuner choose — resolved through the decision cache at
    # build_model time, NTS_TUNE=cached|measure). Env override
    # NTS_WIRE_DTYPE (parallel/ring_schedule.resolve_wire_dtype).
    ell_levels: str = ""  # BlockedEll level-ladder policy for the fused
    # edge tables (ops/blocked_ell.resolve_levels): "" (the path default:
    # binned for single-chip fused tables, pow2 for the ring stacked
    # tables), pow2, binned, or auto (tune/ autotuner). NTS_ELL_LEVELS
    # env keeps its historical precedence for non-auto values.
    kernel_tile: int = 0  # OPTIM_KERNEL source-tile width (vertices): 0 =
    # plain ELL; >0 = blocked ELL (ops/blocked_ell.py) whose per-tile gather
    # table [vt, f] is sized to stay in the fast on-chip regime at any V
    kernel: str = ""  # KERNEL: named-kernel selector for the attention/
    # edge-op families: "" (the eager edge chain) or fused_edge (the
    # blocked streaming SDDMM+softmax+SpMM kernel, ops/fused_edge.py —
    # online per-dst softmax, no [Ep, f] edge tensors). Serves GAT / GGCN
    # and their dist twins; anything else refuses loudly at the
    # ToolkitBase lifecycle funnel (the DIST_PATH refusal pattern).
    # KERNEL_TILE doubles as its source-tile height.
    pallas_kernel: bool = False  # OPTIM_KERNEL:1 + PALLAS:1 -> run the
    # aggregation through the fused streamed block-sparse Pallas kernel
    # (ops/bsp_ell.py — the one fused design Mosaic can compile: one-hot
    # MXU gather + scatter, no unsupported row gathers) at any scale;
    # KERNEL_TILE:vt sets its src-tile height (default DEFAULT_VT). On the
    # dist path PALLAS:1 runs the same compiled Mosaic bsp kernel per
    # shard over the all_gathered slab (parallel/dist_bsp.py).
    edge_chunk: int = 0  # scatter-path edge chunk size (0 = auto); applies
    # to the chunked-scatter layouts (DeviceGraph, DistGraph) — the ELL and
    # mirror-slot layouts have their own slot sizing. Tests/dryruns set it
    # small to force the multi-chunk scan regime.
    # Online inference serving (serve/; docs/SERVING.md). Every knob has an
    # NTS_SERVE_* env override (launcher parity, like NTS_PARTITIONS_OVERRIDE)
    # resolved in serve.batcher.ServeOptions.from_cfg.
    serve_max_batch: int = 16  # micro-batch flush size == largest AOT bucket
    serve_max_wait_ms: float = 5.0  # deadline coalescing window per flush
    serve_max_queue: int = 256  # pending-request bound; beyond it: shed
    serve_buckets: str = ""  # dash-separated AOT bucket ladder override
    # (SERVE_BUCKETS:1-4-16); "" = geometric x4 ladder up to max_batch
    serve_cache_cap: int = 0  # inference embedding cache entries (0 = off)
    serve_cache_max_age_s: float = 60.0  # cache staleness bound (seconds)
    serve_hot_threshold: int = 0  # out-degree >= threshold => cacheable
    serve_replicas: int = 1  # serve-fleet size (serve/fleet.py ReplicaSet)
    serve_route: str = ""  # fleet routing policy: least_burn | round_robin
    serve_cb: int = 0  # continuous batching: produce next bucket while
    # the current one executes (SERVE_CB:1; serve/batcher.py)
    # ("hot", the feature_cache hot/cold split rule); 0 = every vertex
    sample_pipeline: str = ""  # SAMPLE_PIPELINE: sampling execution mode
    # for the sampled path (training gcn_sample + serve/): "" / sync (the
    # in-step-loop host sampler — the parity oracle), pipelined (K-deep
    # prefetching background pipeline + async H2D, sample/pipeline.py;
    # bitwise-identical batches to sync), device (pipelined + the jitted
    # on-device uniform hop sampler, sample/device_sampler.py —
    # distribution-equivalent, not bitwise), fused (the whole
    # draw->remap->gather->train batch in ONE jitted program over the
    # resident tables, epochs scanned into one dispatch with zero
    # per-batch H2D, sample/fused.py — distribution-equivalent, bitwise
    # deterministic across reruns), or auto (tuner-resolved like
    # KERNEL:auto, tune/select.py). Env override NTS_SAMPLE_PIPELINE
    # (sample.pipeline.resolve_sample_pipeline).

    # The token-sequence family (ALGORITHM:SEQLM, models/seqlm.py). The
    # model is described by the source's own ``config.json`` keys in the
    # JSON file MODEL_FILE names; the cut to one chip's share is here.
    model_file: str = ""  # MODEL_FILE: the published config.json, as JSON
    token_file: str = ""  # TOKEN_FILE: a .npy of token ids; "" = drawn
    # from the seed, uniform over the vocabulary slice
    seq_layers: int = 0  # SEQ_LAYERS: layers kept, the leading dense ones
    # first (0 = the model's num_hidden_layers)
    seq_length: int = 0  # SEQ_LENGTH: tokens a sequence (0 = the model's
    # max_position_embeddings)
    seq_batch: int = 1  # SEQ_BATCH: sequences an optimizer step
    seq_corpus: int = 8  # SEQ_CORPUS: batches resident on the device,
    # cycled (with TOKEN_FILE: the file's sequences cut into batches)
    expert_shards: int = 1  # EXPERT_SHARDS: chips that share each layer's
    # routed experts; this chip holds n_routed_experts / EXPERT_SHARDS
    expert_shard: int = 0  # EXPERT_SHARD: which of them this chip is
    vocab_shards: int = 1  # VOCAB_SHARDS: chips that share the vocabulary;
    # ids, logits and loss are over this chip's vocab_size / VOCAB_SHARDS rows
    attn_block: int = 0  # ATTN_BLOCK: positions a tile of the streamed
    # causal attention (0 = ops/causal_attention.DEFAULT_BLOCK)
    loss_chunk: int = 0  # LOSS_CHUNK: tokens a chunk of the head + loss
    # (0 = 4096): the [tokens, vocab] logits never exist whole
    kda_chunk: int = 0  # KDA_CHUNK: positions a chunk of the delta rule
    # (0 = ops/delta_rule.DEFAULT_CHUNK); a model without such layers ignores it
    warmup_epochs: int = 0  # WARMUP_EPOCHS: optimizer steps over which the
    # learn rate rises linearly from 0 to LEARN_RATE (0 = none), as a
    # language-model pre-training job starts; SEQLM honours it

    @staticmethod
    def read_from_cfg_file(path: str) -> "InputInfo":
        """Parse a flat KEY:VALUE cfg file (GraphSegment.cpp:222-292)."""
        cfg = InputInfo()
        with open(path, "r") as fh:
            for raw in fh:
                line = raw.strip()
                if not line or line.startswith("#"):
                    continue
                if ":" not in line:
                    continue
                key, _, value = line.partition(":")
                key = key.strip().upper()
                value = value.strip()
                cfg._apply(key, value)
        return cfg

    # keep the reference's camel-ish name available too
    readFromCfgFile = read_from_cfg_file

    def _apply(self, key: str, value: str) -> None:
        if key == "ALGORITHM":
            self.algorithm = value
        elif key == "VERTICES":
            self.vertices = int(value)
        elif key == "EPOCHS":
            self.epochs = int(value)
        elif key == "BATCH_SIZE":
            self.batch_size = int(value)
        elif key == "LAYERS":
            self.layer_string = value
        elif key == "FANOUT":
            self.fanout_string = value
        elif key == "EDGE_FILE":
            self.edge_file = value
        elif key == "FEATURE_FILE":
            self.feature_file = value
        elif key == "LABEL_FILE":
            self.label_file = value
        elif key == "MASK_FILE":
            self.mask_file = value
        elif key == "LEARN_RATE":
            self.learn_rate = float(value)
        elif key == "WEIGHT_DECAY":
            self.weight_decay = float(value)
        elif key == "DECAY_RATE":
            self.decay_rate = float(value)
        elif key == "DECAY_EPOCH":
            self.decay_epoch = int(value)
        elif key == "DROP_RATE":
            self.drop_rate = float(value)
        elif key == "PROC_OVERLAP":
            self.process_overlap = bool(int(value))
        elif key == "PROC_LOCAL":
            self.process_local = bool(int(value))
        elif key == "PROC_CUDA":
            self.with_cuda = bool(int(value))
        elif key == "PROC_REP":
            self.process_rep = bool(int(value))
        elif key == "LOCK_FREE":
            self.lock_free = bool(int(value))
        elif key == "OPTIM_KERNEL":
            self.optim_kernel = bool(int(value))
        elif key == "KERNEL_TILE":
            self.kernel_tile = int(value)
        elif key == "KERNEL":
            v = value.strip().lower()
            # validated like DIST_PATH/PRECISION: a typo'd value would
            # silently run the eager edge chain while the user benchmarks
            # it as the fused kernel
            if v not in ("", "fused_edge", "auto"):
                raise ValueError(
                    f"KERNEL must be fused_edge or auto (or empty), "
                    f"got {value!r}"
                )
            self.kernel = v
        elif key == "PALLAS":
            self.pallas_kernel = bool(int(value))
        elif key == "PARTITIONS":
            self.partitions = int(value)
        elif key == "PRECISION":
            # validated like CKPT_BACKEND: a typo'd value (bf16, bfloat)
            # would otherwise silently train f32 while the user benchmarks
            # it as bf16 (r5 review)
            if value not in ("float32", "bfloat16"):
                raise ValueError(
                    f"PRECISION must be float32 or bfloat16, got {value!r}"
                )
            self.precision = value
        elif key == "CHECKPOINT_DIR":
            self.checkpoint_dir = value
        elif key == "CHECKPOINT_EVERY":
            self.checkpoint_every = int(value)
        elif key == "CKPT_BACKEND":
            if value not in ("npz", "orbax"):
                raise ValueError(
                    f"CKPT_BACKEND must be npz or orbax, got {value!r}"
                )
            self.ckpt_backend = value
        elif key == "REP_THRESHOLD":
            # "auto" -> -1: the cache build chooses the smallest threshold
            # whose replicated rows fit CACHE_BUDGET_MIB (the automatic
            # hybrid dependency decision; see CachedMirrorGraph.
            # choose_replication_threshold)
            self.rep_threshold = -1 if value.lower() == "auto" else int(value)
        elif key == "CACHE_BUDGET_MIB":
            self.cache_budget_mib = int(value)
        elif key == "CACHE_REFRESH":
            self.cache_refresh = int(value)
        elif key == "SUBLINEAR":
            self.sublinear = bool(int(value))
        elif key == "EDGE_CHUNK":
            self.edge_chunk = int(value)
        elif key in ("MODEL_FILE", "TOKEN_FILE"):
            setattr(self, key.lower(), value)
        elif key in ("SEQ_LAYERS", "SEQ_LENGTH", "SEQ_BATCH", "SEQ_CORPUS",
                     "EXPERT_SHARDS", "EXPERT_SHARD", "VOCAB_SHARDS",
                     "ATTN_BLOCK", "LOSS_CHUNK", "KDA_CHUNK", "WARMUP_EPOCHS"):
            setattr(self, key.lower(), int(value))
        elif key == "COMM_LAYER":
            self.comm_layer = value.strip().lower()
        elif key == "DIST_PATH":
            v = value.strip().lower()
            # validated like PRECISION: a typo'd value would silently run
            # the all_gather path while the user benchmarks it as the ring
            if v not in ("", "auto", "all_gather", "ring_blocked",
                         "ring_blocked_sim"):
                raise ValueError(
                    "DIST_PATH must be auto, all_gather, ring_blocked or "
                    f"ring_blocked_sim, got {value!r}"
                )
            self.dist_path = v
        elif key == "MESH":
            # validated + canonicalized like DIST_PATH: a typo'd shape
            # would silently train the replicated-feature 1D layout while
            # the user benchmarks it as the 2D mesh
            from neutronstarlite_tpu.parallel.partitioner import (
                normalize_mesh_value,
            )

            self.mesh = normalize_mesh_value(value)
        elif key == "WIRE_DTYPE":
            v = value.strip().lower()
            if v not in ("", "f32", "float32", "bf16", "bfloat16", "auto"):
                raise ValueError(
                    f"WIRE_DTYPE must be f32/float32, bf16/bfloat16 or "
                    f"auto, got {value!r}"
                )
            self.wire_dtype = v
        elif key == "ELL_LEVELS":
            v = value.strip().lower()
            # validated like DIST_PATH/KERNEL: a typo'd ladder name would
            # silently run the path default while the user benchmarks the
            # other ladder
            if v not in ("", "pow2", "binned", "auto"):
                raise ValueError(
                    f"ELL_LEVELS must be pow2, binned or auto (or empty), "
                    f"got {value!r}"
                )
            self.ell_levels = v
        elif key == "UNDIRECTED":
            self.undirected = bool(int(value))
        elif key == "DATA_FORMAT":
            self.data_format = value.strip().lower()
        elif key == "SERVE_MAX_BATCH":
            self.serve_max_batch = int(value)
        elif key == "SERVE_MAX_WAIT_MS":
            self.serve_max_wait_ms = float(value)
        elif key == "SERVE_MAX_QUEUE":
            self.serve_max_queue = int(value)
        elif key == "SERVE_BUCKETS":
            self.serve_buckets = value
        elif key == "SERVE_CACHE_CAP":
            self.serve_cache_cap = int(value)
        elif key == "SERVE_CACHE_MAX_AGE_S":
            self.serve_cache_max_age_s = float(value)
        elif key == "SERVE_HOT_THRESHOLD":
            self.serve_hot_threshold = int(value)
        elif key == "SERVE_REPLICAS":
            self.serve_replicas = int(value)
        elif key == "SERVE_ROUTE":
            self.serve_route = value
        elif key == "SERVE_CB":
            self.serve_cb = int(value)
        elif key == "SAMPLE_PIPELINE":
            v = value.strip().lower()
            # validated like DIST_PATH/KERNEL: a typo'd value would
            # silently run the synchronous sampler while the user
            # benchmarks it as the pipeline
            if v not in ("", "sync", "pipelined", "device", "fused",
                         "auto"):
                raise ValueError(
                    f"SAMPLE_PIPELINE must be sync, pipelined, device, "
                    f"fused or auto, got {value!r}"
                )
            self.sample_pipeline = v
        # unknown keys ignored, matching the reference's else-silence

    def layer_sizes(self) -> List[int]:
        """Parse "1433-128-7" -> [1433, 128, 7] (graph.hpp:293-318)."""
        if not self.layer_string:
            return []
        return [int(tok) for tok in self.layer_string.split("-") if tok]

    def fanouts(self) -> List[int]:
        """Parse "5-10-10" -> [5, 10, 10] (graph.hpp:319-336)."""
        if not self.fanout_string:
            return []
        return [int(tok) for tok in self.fanout_string.split("-") if tok]

    def serve_bucket_list(self) -> List[int]:
        """Parse SERVE_BUCKETS:1-4-16 -> [1, 4, 16] (the AOT batch-size
        ladder; empty = derive geometrically, serve.batcher.ServeOptions)."""
        if not self.serve_buckets:
            return []
        return [int(tok) for tok in self.serve_buckets.split("-") if tok]

    def gnn_context(self) -> GNNContext:
        sizes = self.layer_sizes()
        return GNNContext(layer_size=sizes, fanout=self.fanouts())

    def runtime_info(self) -> RuntimeInfo:
        return RuntimeInfo(
            process_local=self.process_local,
            process_overlap=self.process_overlap,
            with_cuda=self.with_cuda,
            process_rep=self.process_rep,
            lock_free=self.lock_free,
            optim_kernel_enable=self.optim_kernel,
            epoch=self.epochs,
        )

    def resolve_path(self, path: str, base_dir: Optional[str] = None) -> str:
        """Resolve data paths relative to the cfg file's directory. An empty
        path stays empty (= "not provided": the datum loader's per-field
        random fallback)."""
        if not path or os.path.isabs(path) or not base_dir:
            return path
        return os.path.normpath(os.path.join(base_dir, path))

    def print(self) -> str:
        """Config echo (reference: InputInfo::print, GraphSegment.cpp:294-318)."""
        lines = [
            f"ALGORITHM: {self.algorithm}",
            f"VERTICES: {self.vertices}",
            f"LAYERS: {self.layer_string}",
            f"FANOUT: {self.fanout_string}",
            f"EPOCHS: {self.epochs}",
            f"BATCH_SIZE: {self.batch_size}",
            f"EDGE_FILE: {self.edge_file}",
            f"FEATURE_FILE: {self.feature_file}",
            f"LABEL_FILE: {self.label_file}",
            f"MASK_FILE: {self.mask_file}",
            f"LEARN_RATE: {self.learn_rate}",
            f"WEIGHT_DECAY: {self.weight_decay}",
            f"DECAY_RATE: {self.decay_rate}",
            f"DECAY_EPOCH: {self.decay_epoch}",
            f"DROP_RATE: {self.drop_rate}",
            f"PROC_OVERLAP: {int(self.process_overlap)}",
            f"PROC_LOCAL: {int(self.process_local)}",
            f"PROC_CUDA: {int(self.with_cuda)}",
            f"LOCK_FREE: {int(self.lock_free)}",
        ]
        return "\n".join(lines)
