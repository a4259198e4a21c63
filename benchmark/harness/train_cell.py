"""Traffic of kind ``train_epochs``: back-to-back epochs through the
trainer's own ``run()``.

``run()`` takes a fixed epoch count, so the cell warms up with a short
``run()`` (which compiles, or finds the cache), sizes the measured
``run()`` from the warm epochs so that it fills ``--seconds``, and makes
that second call the window. The benchmark never drives the train step
itself: what the run loop does between epochs (the loss fetch, the cadence
copy of the logits and the host accuracy) is part of the epoch a user
pays.

Epoch ends are stamped on the benchmark's own clock in a wrapper around
the trainer's ``emit_epoch``, the one call every run loop makes when an
epoch has ended; an epoch's time is the distance between two stamps. The
wrapper also keeps the ``stages`` the program passes there (its own
dispatch / device-wait spans) for the per-layer readers.
"""

from __future__ import annotations

import math
import time
from typing import Any, Dict, List

import numpy as np

from . import correct, data, program, runtime


class EpochClock:
    """Wraps ``trainer.emit_epoch``: stamps, and the program's stages."""

    def __init__(self, trainer) -> None:
        self.stamps: List[float] = []
        self.stages: List[Dict[str, float]] = []
        self.at_stamp: Dict[int, Any] = {}  # stamp count -> call made there
        self._inner = trainer.emit_epoch
        trainer.emit_epoch = self

    def __call__(self, epoch, seconds, loss=None, stages=None, **extra):
        self.stamps.append(time.perf_counter())
        self.stages.append(dict(stages or {}))
        hook = self.at_stamp.pop(len(self.stamps), None)
        if hook is not None:
            hook()
        return self._inner(epoch, seconds, loss, stages=stages, **extra)


def build_trainer(ctx):
    """((feature, label, mask), trainer): the seed's datum and the
    program's trainer over it and the configuration's host graph (cached),
    each under a span of the benchmark's own."""
    config, spans = ctx.config, ctx.spans
    t = time.perf_counter()
    graph, cached = program.host_graph(
        data.graph_params(config, ctx.rehearse), ctx.cache_root
    )
    spans["graph_s"] = time.perf_counter() - t
    spans["graph_cached"] = float(cached)
    runtime.log(f"host graph V={graph.v_num} E={graph.e_num} "
                f"({'cache' if cached else 'built'}, {spans['graph_s']:.1f}s)")

    t = time.perf_counter()
    cfg = program.read_cfg(config, ctx.work_dir, ctx.rehearse)
    sizes = cfg.layer_sizes()
    feature, label, mask = data.make_datum(
        graph.v_num, sizes[0], sizes[-1], data_split(config, graph.v_num), ctx.seed
    )
    spans["datum_s"] = time.perf_counter() - t

    t = time.perf_counter()
    trainer = program.build_trainer(cfg, graph, feature, label, mask, ctx.seed)
    spans["trainer_build_s"] = time.perf_counter() - t
    runtime.log(f"trainer {type(trainer).__name__} built in {spans['trainer_build_s']:.1f}s")
    return (feature, label, mask), trainer


def sampled_cases(trainer, label: np.ndarray, mask: np.ndarray, seed: int, ref) -> List[Dict[str, Any]]:
    """Blocks the fused sampler draws for seeded batches of training
    vertices (the second one half full, as an epoch's last batch is), with
    the trainer's eval logits on them and, for the first, its gradients."""
    import jax

    batch = int(trainer.cfg.batch_size)
    train_ids = np.where(mask == 0)[0]
    rng = np.random.default_rng(seed)
    cases = []
    for i in range(correct.SAMPLE_BATCHES):
        n_real = batch if i != 1 else batch // 2
        seeds = np.zeros(batch, dtype=np.int32)
        seeds[:n_real] = rng.choice(train_ids, size=n_real, replace=False)
        case = program.sampled_case(trainer, seeds, n_real, jax.random.PRNGKey(seed * 1000 + i))
        if i == 0:
            case.update(label=label[seeds], mask01=(np.arange(batch) < n_real).astype(np.float32))
            _, case["grads"] = program.eval_loss_and_grads(
                trainer, ref.masked_nll, (case["label"], case["mask01"]),
                (case["nodes"], case["hops"]),
            )
        cases.append(case)
    return cases


def run_cell(ctx) -> Dict[str, Any]:
    """Builds the trainer, warms up, measures, checks. Returns the cell's
    record: what the metric readers read."""
    traffic, config, spans = ctx.traffic, ctx.config, ctx.spans
    (feature, label, mask), trainer = build_trainer(ctx)
    clock = EpochClock(trainer)
    family = program.trainer_family(trainer)

    # warm-up: the first epoch compiles (or loads the cache); the rest are warm
    warmup = int(traffic["warmup_epochs"])
    t_warm = time.perf_counter()
    trainer.cfg.epochs = warmup
    trainer.run()
    spans["warmup_s"] = time.perf_counter() - t_warm
    spans["first_epoch_s"] = clock.stamps[0] - t_warm
    warm = np.diff(clock.stamps[:warmup])
    warm_epoch_s = float(np.median(warm))
    seconds = float(traffic["trace_seconds"]) if ctx.trace else ctx.seconds
    n_epochs = max(int(traffic["min_epochs"]), math.ceil(seconds / warm_epoch_s))
    if ctx.trace:
        n_epochs = min(n_epochs, int(traffic["trace_max_epochs"]))
    runtime.log(f"warm epoch {warm_epoch_s:.4f}s -> window of {n_epochs} epochs")

    # the window
    h2d_before = program.counter(trainer, "sample.h2d_bytes")
    trainer.cfg.epochs = n_epochs
    if ctx.trace:
        # the traced window ends with its last epoch, not with what run()
        # does after it (the sampled trainer's closing evaluation)
        clock.at_stamp[warmup + n_epochs] = ctx.stop_profiler
        ctx.profiler.start()
    t0 = time.perf_counter()
    trainer.run()
    stamps = clock.stamps[warmup:]
    t1 = stamps[-1]
    memory_peak = runtime.memory_peak_bytes(ctx.chips)

    epoch_times = np.diff([t0] + stamps)
    losses = [float(v) for v in trainer.loss_history]
    record: Dict[str, Any] = {
        "window": (t0, t1),
        "epoch_times": [float(v) for v in epoch_times],
        "epochs": n_epochs,
        "stages": clock.stages[warmup:],
        "losses": losses,
        "memory_peak_bytes": memory_peak,
        "sample_h2d_bytes": program.counter(trainer, "sample.h2d_bytes") - h2d_before,
        "shape": program.shape_facts(trainer),
        "family": family,
        "attempted": n_epochs,
        "failed": int(sum(1 for v in losses[warmup:] if not math.isfinite(v))),
    }

    # correctness, outside the window and after the memory reading
    t = time.perf_counter()
    params = program.host_params(trainer)
    ref_graph = correct.ReferenceGraph(
        config, data.graph_params(config, ctx.rehearse), ctx.cache_root
    )
    if family == "sampled":
        check = correct.check_blocks(
            ref_graph, params, feature,
            sampled_cases(trainer, label, mask, ctx.seed, ref_graph.ref),
        )
    else:
        train01 = (mask == 0).astype(np.float32)
        grads = None
        if family == "fullbatch":  # one device holds the reference's backward pass
            _, grads = program.eval_loss_and_grads(
                trainer, ref_graph.ref.masked_nll, (label, train01)
            )
        check = correct.check_whole_graph(
            ref_graph, params, feature, label, train01, program.eval_logits(trainer),
            grads, ctx.seed,
        )
    check["losses_finite"] = correct.losses_finite(losses)
    tolerance = correct.tolerance(config, ctx.rehearse)
    spans["check_s"] = time.perf_counter() - t
    record["check"] = check
    record["correct"] = correct.passes(check, tolerance)
    runtime.log(f"check {check} against {tolerance['logits_rel']} / "
                f"{tolerance.get('grads_rel')} in {spans['check_s']:.1f}s; losses "
                f"{losses[0]:.4f} -> {losses[-1]:.4f}")
    return record


def data_split(config: dict, vertices: int) -> List[int]:
    """The configuration's (train, val, test) sizes; a rehearsal's smaller
    graph keeps their proportions."""
    split = [int(s) for s in config["data"]["split"]]
    if sum(split) == vertices:
        return split
    scaled = [s * vertices // sum(split) for s in split]
    scaled[0] += vertices - sum(scaled)
    return scaled
