"""Every compile as a span: what JAX reports of its compiler, attributed.

JAX tells ``jax.monitoring`` listeners, for every request of its compiler
and in this order on the thread that makes it: the seconds it traced the
function (``jaxpr_trace_duration``, with the function's name: ``_step``),
the seconds it lowered it (``jaxpr_to_mlir_module_duration``:
``jit(_step)``), where a persistent cache is asked that it was asked
(``compile_requests_use_cache``) and whether it held the program
(``cache_hits`` with ``cache_retrieval_time_sec``, or ``cache_misses``),
and the seconds in the backend (``backend_compile_duration``), which wraps
the cache: on a hit it IS the read of the cache and the load of the
program, retrieval included, so the two are never added. One process-wide
listener (``install()``, from ``utils.platform.configure_compile_cache``,
which every entry point calls before its first program) puts the events
of one request together and, when the backend's duration arrives, emits
one span through the tracer under which the calling thread has a span
open (obs/trace ``on_thread()``):

    name ``compile``, cat ``compile``, parent = that innermost open span
    fun        JAX's name of the program, e.g. ``jit(_step)``
    trace_s    seconds tracing the function that is lowered (a jitted
               function called inside it reports a duration of its own,
               inside the outer one's, and so does what the lowering
               traces on its way: the last of the function's name before
               the lowering began counts, never a sum); 0 where the trace
               was cached
    lower_s    seconds lowering to MLIR
    backend_s  seconds in ``compile_or_get_cached``: the compile, or on a
               hit the cache read and the program's load
    retrieve_s seconds of ``backend_s`` reading the cache (0 on a miss)
    cache      ``hit`` | ``miss`` | ``off`` (no persistent cache was asked)
    dur_s = trace_s + lower_s + backend_s; t0 = the stamp at the last
    event less dur_s

The span is retroactive, so it is no ``TraceAnnotation``; its ``t0`` and
``dur_s`` are on ``time.perf_counter``, the clock every live span shares
with the profiler through its annotation, and a compile inside a traced
window lies inside the ``nts:step_dispatch`` annotation of its parent.

Counters on the registry of that tracer (kept under ``NTS_TRACE=0`` too,
where no tracer keeps a stack and every request counts on the newest
registry): ``compile.requests``, ``compile.cache_hits``, ``compile.cache_misses``,
``compile.trace_s``, ``compile.lower_s``, ``compile.backend_s``,
``compile.retrieve_s``; ``run_summary.compile_cache`` is their snapshot. A
request made while the thread has no span of the program open (a caller's
own programs, after ``run()`` has closed its root) emits no span and
counts nowhere: the flight ring and the counters hold the program's
compiles, not its caller's. Nothing here runs per step: JAX calls the
listener only when it compiles, and nothing it does can fail a compile
(an exception is logged and dropped).
"""

from __future__ import annotations

import collections
import threading
import time
from typing import Any, Dict

from neutronstarlite_tpu.obs import trace
from neutronstarlite_tpu.utils.logging import get_logger

log = get_logger("obs")

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND = "/jax/core/compile/backend_compile_duration"
REQUEST = "/jax/compilation_cache/compile_requests_use_cache"
HIT = "/jax/compilation_cache/cache_hits"
RETRIEVE = "/jax/compilation_cache/cache_retrieval_time_sec"

COUNTERS = ("requests", "cache_hits", "cache_misses", "trace_s", "lower_s",
            "backend_s", "retrieve_s")
TRACES_KEPT = 64  # a thread's newest traces no lowering has claimed yet


class _Thread(threading.local):
    """What the calling thread's compiler has reported and no span holds
    yet. ``traced`` outlives a request: a compile made while an outer
    function is traced or lowered ends a request of its own and leaves
    the outer one's trace where its lowering will look for it."""

    def __init__(self) -> None:
        self.traced = collections.deque(maxlen=TRACES_KEPT)  # (ended, function, seconds)
        self.req: Dict[str, Any] = {}


_pending = _Thread()
_installed = False


def _guarded(listener):
    """Telemetry never fails a compile: what ``listener`` raises is logged."""

    def guarded(*args: Any, **kwargs: Any) -> None:
        try:
            listener(*args, **kwargs)
        except Exception as e:
            log.warning("compile telemetry failed in %s (%s); continuing", listener.__name__, e)

    return guarded


@_guarded
def _on_event(name: str, **_: Any) -> None:
    if name == REQUEST:
        _pending.req["cache"] = "miss"  # until ``cache_hits`` says otherwise
    elif name == HIT:
        _pending.req["cache"] = "hit"


@_guarded
def _on_duration(name: str, seconds: float, fun_name: str = "", **_: Any) -> None:
    seconds = float(seconds)
    if name == TRACE:
        _pending.traced.append((time.perf_counter(), fun_name, seconds))
    elif name == LOWER:
        # the function's own trace ended before its lowering began (what a
        # lowering traces on its way, a kernel's body, reports inside it);
        # a lowering whose trace was cached finds no trace of its name
        began = time.perf_counter() - seconds
        own = [t for t in _pending.traced if t[0] <= began and fun_name.endswith(f"({t[1]})")]
        if own:
            _pending.traced.remove(own[-1])
        _pending.req = {"fun": fun_name, "lower_s": seconds, "trace_s": own[-1][2] if own else 0.0}
    elif name == RETRIEVE:
        _pending.req["retrieve_s"] = seconds
    elif name == BACKEND:
        req, _pending.req = _pending.req, {}
        if req.get("fun") != fun_name:  # compiled from a lowering made earlier
            req = {k: req[k] for k in ("cache", "retrieve_s") if k in req}
        _finish(fun_name, seconds, req)


def _finish(fun: str, backend_s: float, req: Dict[str, Any]) -> None:
    now = time.perf_counter()
    tracer = trace.on_thread()
    if tracer is None:
        tracer = trace.newest()
        # a disabled tracer keeps no stack: every request counts as the run's
        if tracer is None or tracer.enabled:
            return
    parts = {
        "trace_s": req.get("trace_s", 0.0), "lower_s": req.get("lower_s", 0.0),
        "backend_s": backend_s, "retrieve_s": req.get("retrieve_s", 0.0),
    }
    cache = req.get("cache", "off")
    registry = tracer.registry
    registry.counter_add("compile.requests")
    if cache != "off":
        registry.counter_add("compile.cache_hits" if cache == "hit" else "compile.cache_misses")
    for key, seconds in parts.items():
        registry.counter_add("compile." + key, seconds)
    dur_s = parts["trace_s"] + parts["lower_s"] + backend_s
    tracer.complete("compile", dur_s, end=now, cat="compile", fun=fun, cache=cache, **parts)


def install() -> None:
    """Register the listener with ``jax.monitoring``, once a process."""
    global _installed
    if _installed:
        return
    from jax import monitoring

    monitoring.register_event_listener(_on_event)
    monitoring.register_event_duration_secs_listener(_on_duration)
    _installed = True


def snapshot(registry) -> Dict[str, float]:
    """The ``compile.*`` counters of ``registry`` under their short names
    (``hits`` and ``misses`` for the two cache counters)."""
    short = {"cache_hits": "hits", "cache_misses": "misses"}
    return {
        short.get(key, key): registry.counter_get("compile." + key) for key in COUNTERS
    }
