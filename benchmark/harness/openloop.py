"""Open-loop load: a seeded schedule of requests, sent when they are due
whatever the system has answered so far, as independent users send them.

One general generator reads a mix's parameters (benchmark/traffic/*.json):

- ``rate_rps``: the mean rate offered, fixed in the file.
- ``arrivals``: ``{"process": "poisson"}``, or ``{"process": "onoff",
  "on_s": a, "period_s": p}`` for bursts: Poisson at ``rate * p / a``
  during the first ``a`` seconds of every period and silence after, so the
  mean stays ``rate_rps``.
- ``vertex``: ``{"dist": "zipf", "s": s}`` (rank r of a seeded permutation
  of the vertices has mass 1 / r**s: hot vertices) or ``{"dist":
  "uniform"}``.
- ``seeds_per_request``: ``values`` and their ``weights``.

A request's latency runs from when it was due, not from when the
generator got round to sending it, so a stall counts against every
request it delayed; how late the generator ran is reported beside it. A
request that is refused, fails or is not answered within ``timeout_s``
counts as failed and, in the percentiles, as having taken ``timeout_s``.
"""

from __future__ import annotations

import dataclasses
import queue
import threading
import time
from typing import Any, Callable, List

import numpy as np


@dataclasses.dataclass
class Schedule:
    due_s: np.ndarray  # [n] seconds from the window's start, ascending
    ids: List[np.ndarray]  # [n] vertex ids of each request


def arrival_times(rng: np.random.Generator, rate: float, seconds: float,
                  arrivals: dict) -> np.ndarray:
    """Arrival times in [0, seconds) of a Poisson process of mean rate
    ``rate``, homogeneous or on/off."""
    process = arrivals.get("process", "poisson")
    n_draw = int(rate * seconds * 1.5 + 64 * (rate * seconds) ** 0.5 + 64)
    unit = np.cumsum(rng.exponential(1.0, size=n_draw))  # unit-rate arrivals
    if process == "poisson":
        t = unit / rate
    elif process == "onoff":
        on_s, period_s = float(arrivals["on_s"]), float(arrivals["period_s"])
        on_time = unit / (rate * period_s / on_s)  # seconds of "on" elapsed
        t = (on_time // on_s) * period_s + (on_time % on_s)
    else:
        raise ValueError(f"unknown arrival process {process!r}")
    if t[-1] < seconds:
        raise RuntimeError("arrival draw fell short of the window")
    return t[t < seconds]


def vertex_sampler(rng: np.random.Generator, vertices: int, vertex: dict) -> Callable[[int], np.ndarray]:
    dist = vertex.get("dist", "uniform")
    if dist == "uniform":
        return lambda n: rng.integers(0, vertices, size=n)
    if dist == "zipf":
        mass = 1.0 / np.arange(1, vertices + 1, dtype=np.float64) ** float(vertex["s"])
        cdf = np.cumsum(mass / mass.sum())
        by_rank = rng.permutation(vertices)
        return lambda n: by_rank[np.minimum(np.searchsorted(cdf, rng.random(n)), vertices - 1)]
    raise ValueError(f"unknown vertex distribution {dist!r}")


def make_schedule(seed: int, mix: dict, vertices: int, seconds: float,
                  rate: float = None) -> Schedule:
    """The same seed, mix and window give the same schedule."""
    rng = np.random.default_rng(seed)
    rate = float(mix["rate_rps"] if rate is None else rate)
    due = arrival_times(rng, rate, seconds, mix.get("arrivals", {}))
    sizes_spec = mix["seeds_per_request"]
    weights = np.asarray(sizes_spec["weights"], dtype=np.float64)
    sizes = rng.choice(np.asarray(sizes_spec["values"]), size=len(due), p=weights / weights.sum())
    draw = vertex_sampler(rng, vertices, mix.get("vertex", {}))
    flat = draw(int(sizes.sum())).astype(np.int64)
    ids = np.split(flat, np.cumsum(sizes)[:-1])
    return Schedule(due_s=due, ids=ids)


@dataclasses.dataclass
class Outcome:
    t0: float  # perf_counter at the window's start
    due: np.ndarray  # [n] perf_counter times
    sent: np.ndarray
    done: np.ndarray  # nan where the request got no answer
    ok: np.ndarray  # bool
    n_seeds: np.ndarray
    requests: List[Any]  # what submit() returned, in order

    def latency_ms(self, missing_ms: float) -> np.ndarray:
        """From due time to answer; ``missing_ms`` (the time-out: such a
        request misses any limit) where there was no answer."""
        lat = (self.done - self.due) * 1000.0
        return np.where(self.ok, lat, missing_ms)

    @property
    def late_ms(self) -> np.ndarray:
        return (self.sent - self.due) * 1000.0


def run_schedule(schedule: Schedule, submit: Callable[[np.ndarray], Any],
                 timeout_s: float) -> Outcome:
    """Sends every request at its due time from one thread, collects the
    answers in order on another, and returns when all are in. ``submit``
    returns at once with an object whose ``result(timeout)`` blocks for the
    answer or raises."""
    n = len(schedule.due_s)
    sent = np.full(n, np.nan)
    done = np.full(n, np.nan)
    ok = np.zeros(n, dtype=bool)
    requests: List[Any] = [None] * n
    handoff: "queue.SimpleQueue" = queue.SimpleQueue()
    t0 = time.perf_counter() + 0.05  # both threads are up before the first is due
    due = t0 + schedule.due_s

    def sender() -> None:
        for i in range(n):
            wait = due[i] - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            req = submit(schedule.ids[i])
            sent[i] = time.perf_counter()
            requests[i] = req
            handoff.put(i)

    def collector() -> None:
        for _ in range(n):
            i = handoff.get()
            try:
                requests[i].result(timeout_s)
                ok[i] = True
            except Exception:  # shed, failed or timed out: no latency
                ok[i] = False
            done[i] = time.perf_counter()

    threads = [
        threading.Thread(target=sender, name="bench-sender"),
        threading.Thread(target=collector, name="bench-collector"),
    ]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    return Outcome(
        t0=t0, due=due, sent=sent, done=done, ok=ok,
        n_seeds=np.asarray([len(x) for x in schedule.ids]), requests=requests,
    )
