"""Inference engine: digest-verified checkpoint -> AOT bucket executables.

Turns a trained sampled-GCN toolkit into an online scorer:

1. **Checkpoint load.** The model is reconstructed through the trainer's
   own lifecycle (``get_algorithm`` -> ``init_graph``/``init_nn``) and the
   weights restored via utils/checkpoint.py — the same digest-verified,
   quarantine-on-corruption restore path training resume uses, so a
   bit-flipped checkpoint can never silently serve garbage.

2. **Eval-mode forward.** The per-bucket forward is the exact eval-mode
   computation of the sampled trainer (models/gcn_sample.py
   ``batch_forward`` with ``train=False``): feature gather ->
   per-hop ``minibatch_gather`` + matmul (+ relu between layers), dropout
   compiled out entirely. Served logits are therefore bit-identical to the
   toolkit's own eval forward on the same sampled batch (the parity oracle
   in tests/test_serve.py).

3. **AOT shape buckets.** Request batches vary in size, but XLA recompiles
   per shape — fatal for tail latency. So a small ladder of batch-size
   buckets (ServeOptions.ladder) is compiled ahead of time via
   ``jax.jit(...).lower(...).compile()``; every flush pads to the smallest
   covering bucket and replays that executable. ``compile_counts`` proves
   the discipline: exactly one compilation per bucket, ever — the
   fixed-shape compile-once design the sampler's padded capacities were
   built for (SURVEY.md "pad to fanout capacity ... to avoid
   recompilation"; Accel-GCN's fixed-shape execution argument).
"""

from __future__ import annotations

import os
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from neutronstarlite_tpu.ops.minibatch import get_feature, minibatch_gather
from neutronstarlite_tpu.sample.sampler import SampledBatch
from neutronstarlite_tpu.serve.batcher import ServeOptions
from neutronstarlite_tpu.serve.sampling import ServeSampler
from neutronstarlite_tpu.utils.config import InputInfo
from neutronstarlite_tpu.utils.logging import get_logger

log = get_logger("serve")


class ServeSetupError(RuntimeError):
    """Unservable configuration (no checkpoint, unsupported model, ...)."""


def _eval_forward_fn(caps: List[int], compute_dtype):
    """The bucket's eval-mode forward — textually the ``train=False`` path
    of GCNSampleTrainer.build_model's batch_forward (dropout never traced),
    closed over this bucket's node capacities."""

    def cast(a):
        return a.astype(compute_dtype) if compute_dtype is not None else a

    def forward(params, feature, nodes, hops):
        x = cast(get_feature(feature, nodes[0]))
        for i, (p, (src_l, dst_l, w)) in enumerate(zip(params, hops)):
            agg = minibatch_gather(src_l, dst_l, w, x, caps[i + 1])
            h = cast(agg) @ cast(p["W"])
            if i < len(params) - 1:
                h = jax.nn.relu(h)
            x = h
        return x.astype(jnp.float32)  # [bucket, n_classes]

    return forward


def _fused_forward_fn(caps: List[int], fanouts: List[int], compute_dtype):
    """SAMPLE_PIPELINE:fused — the request's WHOLE cache-miss path as one
    program: on-device fan-out draw + dedup/remap (sample/fused.py) feeding
    the same eval-mode forward, so a served bucket is sample+execute in ONE
    dispatch. Operands are the resident tables plus a padded seed vector,
    the live count and a draw key — no per-request subgraph H2D."""
    forward = _eval_forward_fn(caps, compute_dtype)
    from neutronstarlite_tpu.sample.fused import fused_sample_subgraph

    caps_t, fans_t = tuple(int(c) for c in caps), tuple(int(f) for f in fanouts)

    def fused_forward(params, feature, nbr, eff_deg, out_deg, in_deg,
                      seeds_pad, n_real, key):
        nodes, hops = fused_sample_subgraph(
            nbr, eff_deg, out_deg, in_deg, seeds_pad, n_real, key,
            caps_t, fans_t,
        )
        return forward(params, feature, nodes, hops)

    return fused_forward


def batch_device_args(batch: SampledBatch):
    """SampledBatch -> the (nodes, hops) device pytree, one conversion for
    both the AOT lowering and every steady-state call (shapes and dtypes
    must match the compiled executable's avals exactly)."""
    nodes = [jnp.asarray(n) for n in batch.nodes]
    hops = [
        (jnp.asarray(h.src_local), jnp.asarray(h.dst_local),
         jnp.asarray(h.weight))
        for h in batch.hops
    ]
    return nodes, hops


class InferenceEngine:
    """Checkpoint-backed scorer with a ladder of AOT bucket executables."""

    def __init__(
        self,
        toolkit: Any,
        ckpt_dir: str,
        options: Optional[ServeOptions] = None,
        metrics: Any = None,
        rng: Optional[np.random.Generator] = None,
    ):
        self.toolkit = toolkit
        self.cfg = toolkit.cfg
        self.opts = options or ServeOptions.from_cfg(self.cfg)
        self.metrics = metrics if metrics is not None else toolkit.metrics
        # structural check FIRST: an unservable parameter family must fail
        # with this message, not an opaque tree-mismatch inside restore
        self._check_servable(toolkit.params)
        self._restore(ckpt_dir)
        self.params = toolkit.params
        # the raw rows: a full-batch GCN toolkit's ``feature`` is its
        # aggregated table (ToolkitBase.raw_feature)
        self.feature = toolkit.raw_feature
        fanouts = getattr(toolkit, "fanouts", None)
        if not fanouts:
            sizes = self.cfg.layer_sizes()
            fanouts = self.cfg.fanouts()[-(len(sizes) - 1):]
        if not fanouts:
            raise ServeSetupError(
                "serving samples per-request fan-outs; the cfg needs FANOUT"
            )
        self.fanouts = list(fanouts)
        self.compute_dtype = (
            jnp.bfloat16 if self.cfg.precision == "bfloat16" else None
        )
        hop_sampler = None
        if self.opts.sample_pipeline in ("device", "fused"):
            # SAMPLE_PIPELINE:device — per-request fan-outs draw on-device
            # (sample/device_sampler.py); distribution-equivalent to the
            # host sampler, see docs/SAMPLING.md. fused goes further: the
            # same table feeds the one-dispatch sample+execute program
            # (_fused_forward_fn). The sampled trainer this engine
            # restored through already built the neighbor table for
            # the same mode — reuse it rather than uploading a second copy.
            hop_sampler = getattr(
                getattr(toolkit, "par_sampler", None), "hop_sampler", None
            )
            if hop_sampler is None:
                from neutronstarlite_tpu.sample.device_sampler import (
                    DeviceUniformSampler,
                )

                hop_sampler = DeviceUniformSampler.from_host(
                    toolkit.host_graph
                )
        self.sampler = ServeSampler(
            toolkit.host_graph, self.fanouts, self.opts.ladder(), rng=rng,
            hop_sampler=hop_sampler,
        )
        self.buckets = self.sampler.buckets
        self._compiled: Dict[int, Any] = {}
        # fused ladder: bucket -> (table_shapes, executable). Keyed off the
        # live table shapes so a delta that REBUILT the neighbor table
        # (new V or width) recompiles instead of feeding the executable
        # shape-mismatched operands; in-place row patches keep the program.
        self._fused_compiled: Dict[int, Any] = {}
        # degree vectors shared across clones, re-derived when a delta
        # swaps the host graph (mutated in place so clones see the swap)
        self._fused_shared: Dict[str, Any] = {"graph": None, "degrees": None}
        self.compile_counts: Dict[int, int] = {}
        # shared across clones (serve/fleet.py): two replica executors
        # racing a cold bucket must still compile it exactly once
        self._compile_lock = threading.Lock()

    def clone(self, metrics: Any = None,
              rng: Optional[np.random.Generator] = None) -> "InferenceEngine":
        """A warm replica engine over the SAME toolkit/params/graph.

        The serve fleet's replica N+1 startup path: the clone shares the
        checkpoint-restored params, the feature slab, the device hop
        sampler table, and — crucially — the AOT bucket ladder
        (``_compiled``/``compile_counts`` are the same dicts), so a new
        replica serves its first request with ZERO recompiles; and since
        the toolkit (with its tune-resolved knobs and cached graph
        digest) is shared, nothing is ever re-measured (the PR 9
        decision cache did that work once). Only the ServeSampler is
        fresh: numpy Generators are not thread-safe, so each replica
        draws from its own."""
        new = object.__new__(InferenceEngine)
        new.toolkit = self.toolkit
        new.cfg = self.cfg
        new.opts = self.opts
        new.metrics = metrics if metrics is not None else self.metrics
        new.params = self.params
        new.feature = self.feature
        new.fanouts = list(self.fanouts)
        new.compute_dtype = self.compute_dtype
        new.ckpt_step = self.ckpt_step
        new.sampler = ServeSampler(
            self.sampler.graph, self.fanouts, self.opts.ladder(), rng=rng,
            hop_sampler=self.sampler.hop_sampler,
        )
        new.buckets = new.sampler.buckets
        new._compiled = self._compiled
        new._fused_compiled = self._fused_compiled
        new._fused_shared = self._fused_shared
        new.compile_counts = self.compile_counts
        new._compile_lock = self._compile_lock
        return new

    @property
    def fused(self) -> bool:
        """SAMPLE_PIPELINE:fused — serve cache misses through the
        one-dispatch sample+execute ladder instead of host sample +
        bucket forward."""
        return self.opts.sample_pipeline == "fused"

    def graph_digest(self) -> str:
        """The canonical digest of the graph this engine serves — the
        tune-cache/perf-ledger keying fact a graph delta bumps
        (serve/delta.py updates the toolkit's cached copy)."""
        digest = getattr(self.toolkit, "_tune_graph_digest", None)
        if digest is None:
            from neutronstarlite_tpu.graph.digest import graph_digest

            digest = graph_digest(self.sampler.graph)
            self.toolkit._tune_graph_digest = digest
        return digest

    def apply_delta(self, delta) -> Any:
        """Engine-level delta application (no cache/batcher state — the
        server/fleet paths add those; serve/delta.py has the
        semantics)."""
        from neutronstarlite_tpu.serve import delta as delta_mod

        return delta_mod.apply_to_engines([self], delta)

    # ---- construction ----------------------------------------------------
    @classmethod
    def from_config(
        cls,
        cfg: InputInfo,
        base_dir: Optional[str] = None,
        ckpt_dir: str = "",
        options: Optional[ServeOptions] = None,
        rng: Optional[np.random.Generator] = None,
    ) -> "InferenceEngine":
        """Full lifecycle from a cfg file's contents: load graph + datum,
        build the model, restore the checkpoint."""
        from neutronstarlite_tpu.models import get_algorithm

        ckpt = ckpt_dir or cfg.checkpoint_dir
        if not ckpt:
            raise ServeSetupError(
                "no checkpoint directory: pass one explicitly or set "
                "CHECKPOINT_DIR in the cfg"
            )
        # serving never consumes the training batch stream — suppress the
        # sampled trainer's forked worker pool for this construction
        prev = os.environ.get("NTS_SAMPLE_WORKERS")
        os.environ["NTS_SAMPLE_WORKERS"] = "0"
        try:
            toolkit = get_algorithm(cfg.algorithm)(cfg, base_dir=base_dir)
            toolkit.init_graph()
            toolkit.init_nn()
        finally:
            if prev is None:
                os.environ.pop("NTS_SAMPLE_WORKERS", None)
            else:
                os.environ["NTS_SAMPLE_WORKERS"] = prev
        return cls(toolkit, ckpt, options=options, rng=rng)

    def _restore(self, ckpt_dir: str) -> None:
        from neutronstarlite_tpu.utils.checkpoint import have_checkpoint

        if not ckpt_dir or not have_checkpoint(
            ckpt_dir, getattr(self.cfg, "ckpt_backend", "")
        ):
            raise ServeSetupError(
                f"no checkpoint under {ckpt_dir!r} — train first "
                "(CHECKPOINT_DIR + a run), or point serving at an "
                "existing one"
            )
        step = self.toolkit.restore(ckpt_dir)  # digest-verified restore
        if step == 0 and not have_checkpoint(
            ckpt_dir, getattr(self.cfg, "ckpt_backend", "")
        ):
            # every retained step failed verification and was quarantined
            raise ServeSetupError(
                f"every checkpoint under {ckpt_dir!r} failed integrity "
                "verification (quarantined *.corrupt)"
            )
        self.ckpt_step = step
        log.info("serving checkpoint step %d from %s", step, ckpt_dir)

    # the one parameter family the AOT bucket forward can rebuild today;
    # grows as the engine learns more model forwards
    SERVABLE_FAMILIES = (
        "sampled-GCN (params = [{'W': ...}, ...]; ALGORITHM:GCNSAMPLESINGLE)",
    )

    @staticmethod
    def _param_family(p) -> str:
        """Best-effort name for a parameter tree's model family, so the
        refusal names what the checkpoint IS, not just what it isn't."""
        if not isinstance(p, (list, tuple)) or not p:
            return f"non-layer-list params ({type(p).__name__})"
        keys = set()
        for layer in p:
            if not isinstance(layer, dict):
                return f"layer list with non-dict entries ({type(layer).__name__})"
            keys |= set(layer)
        if "a" in keys:
            return "GAT family (attention vector 'a' present)"
        if "Ws" in keys or "Wd" in keys:
            return "GGCN family (gated edge-NN weights Ws/Wd)"
        if "W1" in keys or "W2" in keys:
            return "GIN family (two-layer MLP W1/W2)"
        if "C" in keys or "H" in keys:
            return "CommNet family (C/H projections)"
        if "bn" in keys:
            return "full-batch GCN family (batch-norm stats present)"
        return f"unrecognized family (layer keys: {sorted(keys)})"

    def _check_servable(self, p) -> None:
        """The engine serves the sampled-GCN parameter family: a list of
        layers each holding exactly one dense ``W``. Anything else (bn
        stats, attention params) would silently skip math — refuse,
        naming the DETECTED family and the supported list."""
        ok = isinstance(p, (list, tuple)) and len(p) > 0 and all(
            isinstance(layer, dict) and set(layer) == {"W"} for layer in p
        )
        if not ok:
            supported = "; ".join(self.SERVABLE_FAMILIES)
            raise ServeSetupError(
                f"ALGORITHM {self.cfg.algorithm!r} checkpoints are not "
                f"servable: detected {self._param_family(p)}; the engine "
                f"supports: {supported}"
            )

    # ---- AOT bucket executables ------------------------------------------
    def warmup(self, buckets: Optional[List[int]] = None) -> None:
        """Compile the executable ladder ahead of traffic (the ladder the
        configured pipeline actually serves through)."""
        for b in buckets if buckets is not None else self.buckets:
            if self.fused:
                self._ensure_fused(int(b))
            else:
                self._ensure_compiled(int(b))

    def _ensure_compiled(self, bucket: int):
        compiled = self._compiled.get(bucket)
        if compiled is not None:
            return compiled
        with self._compile_lock:
            return self._compile_bucket(bucket)

    def _compile_bucket(self, bucket: int):
        compiled = self._compiled.get(bucket)  # a racing clone got here first
        if compiled is not None:
            return compiled
        caps = self.sampler.node_caps(bucket)
        forward = _eval_forward_fn(caps, self.compute_dtype)
        # one host-side sample supplies shape-representative args: padded
        # capacities are static per bucket, so any seed set works. The
        # draw must be RNG-NEUTRAL (state saved + restored): otherwise a
        # warm engine (cloned AOT ladder, zero compiles) and a cold one
        # consume different rng streams and the "one seed replays the
        # serving trace bit-identically" contract breaks between them —
        # the delta oracle compares exactly such a warm/cold pair
        rng_state = self.sampler.rng.bit_generator.state
        try:
            rep = self.sampler.sample(
                bucket, np.zeros(1, np.int64)
            )
        finally:
            self.sampler.rng.bit_generator.state = rng_state
        nodes, hops = batch_device_args(rep)
        t0 = time.perf_counter()
        compiled = jax.jit(forward).lower(
            self.params, self.feature, nodes, hops
        ).compile()
        dt = time.perf_counter() - t0
        self._compiled[bucket] = compiled
        self.compile_counts[bucket] = self.compile_counts.get(bucket, 0) + 1
        if self.metrics is not None:
            self.metrics.counter_add(f"serve.compiles.bucket_{bucket}")
            self.metrics.observe("serve.compile", dt)
            # compiled-program cost attribution (obs/cost): the bucket
            # executable already exists, so cost AND memory analysis are
            # free reads — the real per-bucket HBM envelope next to the
            # ladder's shape math
            from neutronstarlite_tpu.obs.cost import capture_program_cost

            capture_program_cost(
                self.metrics, f"serve.bucket_{bucket}", compiled=compiled,
                bucket=bucket, compile_s=round(dt, 4),
            )
        log.info("AOT-compiled bucket %d (caps %s) in %.3fs", bucket, caps, dt)
        return compiled

    # ---- fused one-dispatch ladder (SAMPLE_PIPELINE:fused) ----------------
    def _fused_exec_tables(self):
        """The live device operand tables of the fused program — read at
        call time, never snapshotted at construction: a graph delta
        patches/rebuilds ``hop_sampler.nbr``/``eff_deg`` in place
        (serve/delta.py) and swaps the host graph, and the next request
        must draw from the post-delta structure."""
        hs = self.sampler.hop_sampler
        shared = self._fused_shared
        g = self.sampler.graph
        if shared["graph"] is not g:
            from neutronstarlite_tpu.sample.fused import degree_tables

            shared["degrees"] = degree_tables(g)
            shared["graph"] = g
        out_deg, in_deg = shared["degrees"]
        return hs.nbr, hs.eff_deg, out_deg, in_deg

    def _ensure_fused(self, bucket: int):
        tables = self._fused_exec_tables()
        shapes = tuple(a.shape for a in tables)
        entry = self._fused_compiled.get(bucket)
        if entry is not None and entry[0] == shapes:
            return entry[1]
        with self._compile_lock:
            entry = self._fused_compiled.get(bucket)
            if entry is not None and entry[0] == shapes:
                return entry[1]
            return self._compile_fused_bucket(bucket, tables, shapes)

    def _compile_fused_bucket(self, bucket: int, tables, shapes):
        caps = self.sampler.node_caps(bucket)
        fn = _fused_forward_fn(caps, self.fanouts, self.compute_dtype)
        seeds = jnp.zeros((bucket,), jnp.int32)
        t0 = time.perf_counter()
        compiled = jax.jit(fn).lower(
            self.params, self.feature, *tables, seeds, np.int32(1),
            jax.random.PRNGKey(0),
        ).compile()
        dt = time.perf_counter() - t0
        self._fused_compiled[bucket] = (shapes, compiled)
        self.compile_counts[bucket] = self.compile_counts.get(bucket, 0) + 1
        if self.metrics is not None:
            self.metrics.counter_add(f"serve.compiles.bucket_{bucket}")
            self.metrics.observe("serve.compile", dt)
            from neutronstarlite_tpu.obs.cost import capture_program_cost

            capture_program_cost(
                self.metrics, f"serve.fused_bucket_{bucket}",
                compiled=compiled, bucket=bucket, compile_s=round(dt, 4),
            )
        log.info(
            "AOT-compiled fused bucket %d (caps %s, sample+execute one "
            "dispatch) in %.3fs", bucket, caps, dt,
        )
        return compiled

    def prepare_fused(self, ids: np.ndarray, bucket: int):
        """The fused flush's produce stage: pad the miss set to the bucket
        and stage (seeds, live count, draw key) — the ONLY per-request
        operands; the subgraph itself never exists host-side. The draw key
        consumes the sampler's shared Generator so a serving trace stays
        replayable end-to-end from one seed (the device-mode contract)."""
        ids = np.asarray(ids, dtype=np.int64).reshape(-1)
        seeds = np.zeros((int(bucket),), dtype=np.int32)
        seeds[: len(ids)] = ids
        key = jax.random.PRNGKey(
            int(self.sampler.rng.integers(0, 2 ** 31 - 1))
        )
        seeds_dev, key_dev = jax.device_put((seeds, key))
        return seeds_dev, np.int32(len(ids)), key_dev

    def execute_fused_prepared(self, prepared, bucket: int,
                               exec_ctx=None) -> np.ndarray:
        """ONE dispatch: on-device draw + remap + gather + forward for a
        prepared fused flush. ``exec_ctx`` is the pipelined server's
        produce-time (executable, params, feature, tables) snapshot."""
        b = int(bucket)
        if exec_ctx is not None:
            compiled, params, feature, tables = exec_ctx
        else:
            compiled = self._ensure_fused(b)
            params, feature = self.params, self.feature
            tables = self._fused_exec_tables()
        seeds, n_real, key = prepared
        out = np.asarray(
            compiled(params, feature, *tables, seeds, n_real, key)
        )
        if self.metrics is not None:
            self.metrics.counter_add(f"serve.fused_dispatches.bucket_{b}")
            from neutronstarlite_tpu.obs import numerics

            if numerics.numerics_enabled():
                numerics.observe_serve_batch(self.metrics, out, b)
        return out

    def fused_predict_rows(self, ids: np.ndarray,
                           bucket: Optional[int] = None) -> np.ndarray:
        """Fresh fused logits [n, n_classes] for arbitrary vertex ids —
        prepare + the one dispatch."""
        ids = np.asarray(ids, dtype=np.int64).reshape(-1)
        b = int(bucket) if bucket is not None \
            else self.sampler.bucket_for(len(ids))
        logits = self.execute_fused_prepared(self.prepare_fused(ids, b), b)
        return logits[: len(ids)]

    # ---- scoring ---------------------------------------------------------
    def prepare_batch(self, batch: SampledBatch):
        """SampledBatch -> device-resident (nodes, hops), the H2D stage of
        the two-stage serve pipeline: issued through ONE ``jax.device_put``
        so the copy is in flight while the previous flush executes."""
        return jax.device_put((
            [np.asarray(n) for n in batch.nodes],
            [(h.src_local, h.dst_local, h.weight) for h in batch.hops],
        ))

    def execute_prepared(self, nodes, hops, bucket: int) -> np.ndarray:
        """Run the bucket's AOT executable over already-device-resident
        batch arrays (the executor stage)."""
        compiled = self._ensure_compiled(int(bucket))
        out = np.asarray(compiled(self.params, self.feature, nodes, hops))
        if self.metrics is not None:
            # numerics plane (NTS_NUMERICS=1): engine stats on every
            # executed request batch — host numpy over the logits the
            # reply already fetched (no extra device sync); a non-finite
            # batch leaves a LOUD tensor_stats record, the gauges track
            # the last batch either way
            from neutronstarlite_tpu.obs import numerics

            if numerics.numerics_enabled():
                numerics.observe_serve_batch(self.metrics, out, bucket)
        return out

    def forward_batch(self, batch: SampledBatch,
                      bucket: Optional[int] = None) -> np.ndarray:
        """Logits [bucket, n_classes] for a prepared SampledBatch (rows
        beyond the real seed count are padding)."""
        b = int(bucket) if bucket is not None else len(batch.seeds)
        nodes, hops = batch_device_args(batch)
        return self.execute_prepared(nodes, hops, b)

    def predict(self, node_ids: np.ndarray) -> np.ndarray:
        """Fresh-sampled logits [n, n_classes] for arbitrary vertex ids."""
        ids = np.asarray(node_ids, dtype=np.int64).reshape(-1)
        bucket = self.sampler.bucket_for(len(ids))
        if self.fused:
            return self.fused_predict_rows(ids, bucket)
        batch = self.sampler.sample(bucket, ids)
        logits = self.forward_batch(batch, bucket)
        return logits[: len(ids)]
