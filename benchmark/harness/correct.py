"""The comparison that decides ``correct``: what every check shares.

A configuration names its check (``checks/<name>.py``, found by
harness/spec.py). The check is handed the seeded inputs, the trainer and
the cell's record, compares what the timed path produced with the plain
reference the configuration names (``reference/<name>.py``, which imports
nothing of the program and starts from the benchmark's own inputs), and
returns its errors, each under the name of the limit the configuration's
``tolerance`` states for it, and the faults it can name outright. Here are
the measures of an error, the configuration's limits, and the one rule by
which a run passes: every error has a stated limit and is within it,
every stated limit has an error, and nothing that is compared exactly
(faults named, losses that are not finite, answers malformed) counts
above 0. An error without a limit, or a limit without an error, fails:
a check that stops comparing something does not pass by it.

Logits are compared, not classes: with weights this close to random the
largest logit turns on rounding. A logit error is the largest absolute
difference over the sample relative to the largest absolute reference
logit; a gradient error is the worst, over the weights, of the norm of
the difference relative to the norm of the reference gradient. The
tolerances are the configuration's (``tolerance`` in its file, with the
reasons), per precision: a configuration that states float32 is held to
tolerances that a bfloat16 computation does not meet.
"""

from __future__ import annotations

import importlib
import math
from typing import Any, Dict, List, Optional

import numpy as np


def reference_module(config: dict):
    return importlib.import_module(f"reference.{config['reference']}")


def relative_error(got: np.ndarray, want: np.ndarray) -> float:
    scale = float(np.max(np.abs(want)))
    if not np.all(np.isfinite(got)) or scale == 0.0:
        return float("inf")
    return float(np.max(np.abs(got - want))) / scale


def norm_error(got: np.ndarray, want: np.ndarray) -> float:
    """||got - want|| / ||want||, Euclidean, accumulated in float64."""
    scale = float(np.linalg.norm(np.asarray(want, np.float64)))
    if not np.all(np.isfinite(got)) or scale == 0.0:
        return float("inf")
    return float(np.linalg.norm(np.asarray(got, np.float64) - want)) / scale


def gradient_error(got, want) -> float:
    """The worst ``norm_error`` over the weights (the leaves of two trees
    of one layout). By the norm and not by the largest entry: a weight's
    gradient has tens of thousands of entries, each a short sum of
    bfloat16 products, and the worst of them is an outlier of the
    rounding, not a property of the backward pass."""
    import jax

    return max(jax.tree.leaves(jax.tree.map(norm_error, got, want)))


def unmoved_leaves(before, after) -> List[str]:
    """Names of the leaves of two trees of one layout that are equal entry
    for entry: weights that a number of trained epochs left as they were
    (a step that returns its state unchanged, a leaf the optimizer never
    reaches). A check names them as faults where every leaf is trained."""
    import jax

    pairs = zip(jax.tree_util.tree_leaves_with_path(before), jax.tree.leaves(after))
    return [jax.tree_util.keystr(path) for (path, a), b in pairs
            if np.array_equal(np.asarray(a), np.asarray(b))]


def losses_not_finite(losses: List[float]) -> int:
    """How many of the epochs' training losses are not finite numbers; a
    run with no loss at all counts one. That the loss falls is not
    required: with the configurations' optimizer (Adam at 0.01 on
    batch-normalised inputs, dropout 0.5) the first steps overshoot, and
    over the nine or so epochs of a run the loss rose from 3.89 to 4.57 and
    came back to 3.91 on the chip (PERF.md, section 7). The backward pass
    is held to the reference's gradients instead."""
    return int(np.sum(~np.isfinite(np.asarray(losses, np.float64)))) if len(losses) else 1


def tolerance(config: dict, rehearse: bool) -> Dict[str, float]:
    """The limits the configuration states (its ``tolerance`` less the
    ``reason``); a rehearsal's tiny inputs, on which roundings average out
    over a hundredth of the rows, have their own."""
    tol = dict(config["tolerance"])
    if rehearse:
        tol.update(config["rehearse"].get("tolerance", {}))
    return {k: float(v) for k, v in tol.items() if k != "reason"}


def compare(errors: Dict[str, float], limits: Dict[str, float], **counts: int) -> Dict[str, Dict]:
    """Every number compared beside its limit, by name: the check's errors
    against the configuration's limits (None where either side has no
    such name), and ``counts``, which are compared exactly: limit 0."""
    names = list(errors) + [k for k in limits if k not in errors]
    out: Dict[str, Dict[str, Optional[float]]] = {
        k: {"value": errors.get(k), "limit": limits.get(k)} for k in names
    }
    out.update({k: {"value": int(v), "limit": 0} for k, v in counts.items()})
    return out


def passes(compared: Dict[str, Dict[str, Any]]) -> bool:
    """Every value has a limit and is within it, every limit has a value."""
    return all(
        c["value"] is not None and c["limit"] is not None and c["value"] <= c["limit"]
        for c in compared.values()
    )


def printable(compared: Dict[str, Dict[str, Any]]) -> Dict[str, Dict]:
    """``compared`` for a line of JSON: a number that is not finite goes as
    text, since JSON has no word for it."""
    def plain(v):
        return v if v is None or math.isfinite(v) else str(v)

    return {k: {"value": plain(c["value"]), "limit": plain(c["limit"])}
            for k, c in compared.items()}
