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

**What this file asks of a trainer**, whatever it trains, and all it asks:

- ``cfg.epochs`` can be set, and ``run()`` then trains that many epochs
  more, from the state the last ``run()`` left;
- every run loop calls ``emit_epoch(epoch, seconds, loss, stages=)`` once
  when an epoch has ended (``stages`` may be None);
- ``loss_history`` holds one training loss per epoch trained so far;
- ``metrics.counter_get(name)`` answers a number (0 for a counter the
  trainer never touched);
- ``params`` is a pytree of arrays.

How the trainer and its seeded inputs are built, which sizes its count
needs, and what is compared with which reference belong to the
configuration: ``inputs/<name>.py`` and ``checks/<name>.py``, found by the
names it gives (harness/spec.py). Nothing else of a trainer is reached for
outside those modules.
"""

from __future__ import annotations

import math
import time
from typing import Any, Dict, List

import numpy as np

from harness import correct, program, runtime, spec


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


def run_cell(ctx) -> Dict[str, Any]:
    """Builds the trainer, warms up, measures, checks. Returns the cell's
    record: what the metric readers read."""
    traffic, config, spans = ctx.traffic, ctx.config, ctx.spans
    inputs_of = spec.config_module(config, "inputs")
    inputs, trainer = inputs_of.build(ctx)
    clock = EpochClock(trainer)

    # warm-up: the first epoch compiles (or loads the cache); the rest are warm
    warmup = int(traffic["warmup_epochs"])
    t_warm = time.perf_counter()
    trainer.cfg.epochs = warmup
    trainer.run()
    spans["warmup_s"] = time.perf_counter() - t_warm
    spans["first_epoch_s"] = clock.stamps[0] - t_warm
    warm = np.diff(clock.stamps[:warmup])
    warm_epoch_s = float(np.median(warm))
    # a check that compares a backward pass does so at these weights: the
    # same number of trained epochs however many the window then holds
    warmup_params = program.host_params(trainer)
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
    memory_peak = runtime.memory_peak_bytes(ctx.chips, ctx.rehearse)

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
        "shape": inputs_of.shape(inputs, trainer),
        "warmup_params": warmup_params,
        "attempted": n_epochs,
        "failed": int(sum(1 for v in losses[warmup:] if not math.isfinite(v))),
    }

    # correctness, outside the window and after the memory reading
    t = time.perf_counter()
    errors, faults = spec.config_module(config, "check").check(ctx, inputs, trainer, record)
    spans["check_s"] = time.perf_counter() - t
    record["compared"] = correct.compare(
        errors, correct.tolerance(config, ctx.rehearse),
        faults=len(faults), losses_not_finite=correct.losses_not_finite(losses),
    )
    record["correct"] = correct.passes(record["compared"])
    runtime.log(f"check in {spans['check_s']:.1f}s; faults {faults}; losses "
                f"{losses[0]:.4f} -> {losses[-1]:.4f}")
    return record
