"""Hierarchical span tracing over the MetricsRegistry JSONL stream.

PR 1's flat counters/records can say *how much* (bytes shipped, epochs
timed) but not *when relative to what*: did the ring's hop wait hide under
the blocked-kernel compute, where inside a serve request's p99 did the
time go, what did a resilience retry cost end-to-end. This module adds the
missing causal dimension: every interesting interval becomes one typed
``span`` record (``trace_id`` / ``span_id`` / ``parent_id``, monotonic
begin + duration) written through the SAME per-rank JSONL sink the rest of
obs/ uses — no second telemetry pipe, no new file format, and the existing
``NTS_METRICS_MAX_MB`` / multi-host rank-file conventions apply unchanged.

Clock model (documented in docs/OBSERVABILITY.md):

- ``t0`` is ``time.perf_counter()`` seconds — monotonic, process-local,
  immune to NTP steps mid-run;
- the envelope ``ts`` (wall clock) is stamped when the record is WRITTEN,
  which for spans is immediately after the span ends — so per process the
  mono->wall offset is recoverable as ``median(ts - (t0 + dur_s))`` over
  its spans (tools/trace_timeline does exactly this);
- cross-rank skew is corrected AFTER that mapping by matching the ends
  of the per-epoch ``step_device`` spans (every rank leaves epoch e's
  device wait at the same collective barrier), again in
  tools/trace_timeline — the tracer itself never talks to other ranks.

Every LIVE span (context-manager or ``begin()``/``end()``) is also a
``jax.profiler.TraceAnnotation`` named ``nts:<name>``, with the span's
integer attributes (``epoch``) as event stats. Outside a profiler session
an annotation costs well under a microsecond; inside one — whoever started
it: ``NTS_PROFILE_DIR``, a test, an operator attaching a capture — the
program's spans sit on the profiler's clock beside the device's
operations. The prefix tells them from the runtime's own TraceMe events
(``PjitFunction(train_step)``). Spans emitted retroactively via
``complete()`` (request/queue, the ``startup`` spans that ended before the
process had a tracer, obs/compiles' ``compile`` spans) already happened
and cannot annotate.

The process's tracers are known to this module, newest last
(``newest()``, ``on_thread()``): what happens outside any trainer's call
stack (a compile JAX reports through ``jax.monitoring``, the entry
point's ``start_runtime()``) finds the tracer to emit through, and a span
that ended before any tracer existed waits in ``defer()`` for the first.

Usage::

    tracer = Tracer(registry)
    with tracer.span("graph_load", cat="phase"):
        ...                        # parent = innermost open span (thread-local)
    h = tracer.begin("run", cat="lifecycle")   # long-lived root
    ...
    tracer.end(h, outcome="ok")
    tracer.complete("queue", dur_s=dt, req_id=7)  # retroactive: ended just now

Tracing is on whenever the registry exists (spans are ordinary events; a
sink-less registry keeps them in memory only); ``NTS_TRACE=0`` disables
emission entirely for overhead-sensitive sweeps.
"""

from __future__ import annotations

import itertools
import os
import sys
import threading
import time
import weakref
from typing import Any, Optional

from neutronstarlite_tpu.utils.logging import get_logger, process_index

log = get_logger("obs")


ANNOTATION_PREFIX = "nts:"


def _now() -> float:
    return time.perf_counter()


def _annotation(name: str, attrs: dict):
    """An entered ``TraceAnnotation`` for a live span, or None in a process
    that never imported jax (the hub, the dashboard: no profiler session
    can be active there, and obs/ stays importable without jax)."""
    jax = sys.modules.get("jax")
    if jax is None:
        return None
    ann = jax.profiler.TraceAnnotation(
        ANNOTATION_PREFIX + name,
        **{k: v for k, v in attrs.items() if type(v) is int},
    )
    ann.__enter__()
    return ann


# The process's tracers, newest last (weakly: a tracer lives as long as its
# trainer or server), the newest of them held strongly (as obs/flight holds
# the newest ring: a reader that comes after the trainer is gone still finds
# its registry; a tracer holds no trainer), and the spans that ended before
# the first of them.
_tracers: list = []
_newest: Optional["Tracer"] = None
_deferred: list = []
_tracers_lock = threading.Lock()


def newest() -> Optional["Tracer"]:
    """The newest tracer of the process, enabled or not; None before the
    first."""
    return _newest


def on_thread() -> Optional["Tracer"]:
    """The newest tracer under which the calling thread has a span open
    (a train-then-serve process has two over one registry), or None."""
    with _tracers_lock:
        live = [ref() for ref in reversed(_tracers)]
    return next((t for t in live if t is not None and t.current() is not None), None)


def defer(name: str, t0: float, dur_s: float, cat: str = "host", **attrs: Any) -> None:
    """A span that ended before the process had a tracer (the runtime's
    start): the first tracer made emits it, as a root, when it is made."""
    with _tracers_lock:
        _deferred.append(dict(attrs, name=name, t0=t0, dur_s=dur_s, cat=cat))


# One process-wide id source: several tracers can share one registry (the
# trainer funnel's tracer + the serve server's on a train-then-serve run
# write the SAME per-rank stream), and schema.py documents span_id as
# unique within the stream — per-tracer counters would collide at "s0".
_SPAN_IDS = itertools.count()


class TraceContext:
    """A serializable hop in a distributed trace.

    Three facts cross the process boundary (as HTTP headers, injected by
    obs/httpc and extracted by the exporter's /predict + /telemetry
    handlers):

    - ``trace_id``   — which trace the remote spans should join;
    - ``span_id``    — the CALLER's span the remote spans parent into
      (``parent_id`` on the receiving side);
    - ``send_ts``    — the caller's wall clock at send time.

    The receiver stamps ``recv_ts`` (its own wall clock) at extraction.
    A span emitted with a context therefore carries one (send_ts,
    recv_ts) pair of the two processes' wall clocks taken ~one network
    hop apart — tools/trace_timeline turns the pairs into per-process
    clock offsets (NTP-style, error bounded by RTT/2; see
    docs/OBSERVABILITY.md)."""

    __slots__ = ("trace_id", "span_id", "send_ts", "recv_ts")

    H_TRACE = "X-NTS-Trace-Id"
    H_PARENT = "X-NTS-Parent-Span"
    H_SEND_TS = "X-NTS-Send-Ts"

    def __init__(self, trace_id: str, span_id: Optional[str],
                 send_ts: Optional[float] = None,
                 recv_ts: Optional[float] = None):
        self.trace_id = str(trace_id)
        self.span_id = span_id
        self.send_ts = send_ts
        self.recv_ts = recv_ts

    def to_headers(self, send_ts: Optional[float] = None) -> dict:
        """Header dict for one outbound request. ``send_ts`` defaults to
        now — pass it explicitly to re-stamp per retry attempt."""
        ts = send_ts if send_ts is not None else (
            self.send_ts if self.send_ts is not None else time.time()
        )
        h = {self.H_TRACE: self.trace_id, self.H_SEND_TS: f"{ts:.6f}"}
        if self.span_id:
            h[self.H_PARENT] = self.span_id
        return h

    @classmethod
    def from_headers(cls, headers) -> Optional["TraceContext"]:
        """Parse a received header mapping (anything with ``.get``);
        ``None`` when the request carries no trace. Stamps ``recv_ts``
        with the receiver's wall clock at extraction."""
        trace_id = headers.get(cls.H_TRACE)
        if not trace_id:
            return None
        send_ts: Optional[float] = None
        raw = headers.get(cls.H_SEND_TS)
        if raw:
            try:
                send_ts = float(raw)
            except (TypeError, ValueError):
                send_ts = None
        return cls(trace_id, headers.get(cls.H_PARENT) or None,
                   send_ts=send_ts, recv_ts=time.time())

    def child(self, span_id: Optional[str]) -> "TraceContext":
        """Same trace, re-parented under ``span_id`` (send/recv stamps
        carried along so downstream spans keep the clock pair)."""
        return TraceContext(self.trace_id, span_id,
                            send_ts=self.send_ts, recv_ts=self.recv_ts)

    def __repr__(self) -> str:  # pragma: no cover - debug aid
        return (f"TraceContext({self.trace_id!r}, {self.span_id!r}, "
                f"send_ts={self.send_ts}, recv_ts={self.recv_ts})")


class SpanHandle:
    """One open (or retroactively completed) span."""

    __slots__ = ("name", "cat", "span_id", "parent_id", "t0", "dur_s",
                 "attrs", "trace_id", "_ann", "_ann_tid")

    def __init__(self, name: str, cat: str, span_id: str,
                 parent_id: Optional[str], t0: float, attrs: dict,
                 trace_id: Optional[str] = None):
        self.name = name
        self.cat = cat
        self.span_id = span_id
        self.parent_id = parent_id
        self.t0 = t0
        self.dur_s: Optional[float] = None  # set when a live span ends
        self.attrs = attrs
        self.trace_id = trace_id  # per-span override (remote parenting)
        self._ann = None  # the open jax.profiler annotation, if any
        self._ann_tid = None  # thread that opened it (scopes are TLS)


class Tracer:
    """Span emitter bound to one MetricsRegistry (one trace per run).

    Thread-safe: each thread keeps its own open-span stack, so the serve
    batcher's flusher thread and shedding client threads nest their spans
    independently. Parenting across threads is explicit (``parent=``)."""

    def __init__(self, registry, trace_id: Optional[str] = None):
        self.registry = registry
        self.trace_id = trace_id or (
            registry.run_id if registry is not None else "trace"
        )
        self._tls = threading.local()
        self._rank = process_index()
        self.enabled = (
            registry is not None
            and os.environ.get("NTS_TRACE", "1") != "0"
        )
        if registry is not None:
            global _newest
            with _tracers_lock:
                _newest = self
                _tracers[:] = [r for r in _tracers if r() is not None]
                _tracers.append(weakref.ref(self))
                waiting, _deferred[:] = list(_deferred), []
            for span in waiting:  # NTS_TRACE=0: dropped, as every span is
                self.complete(**span)

    # ---- internals -------------------------------------------------------
    def _stack(self) -> list:
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
        return st

    def _next_id(self) -> str:
        return f"s{next(_SPAN_IDS):x}"

    def current(self) -> Optional[SpanHandle]:
        """The innermost span this thread has open, or None."""
        st = self._stack()
        return st[-1] if st else None

    def _resolve_parent(self, parent) -> tuple:
        """(parent_id, inherited trace override). A child belongs to its
        parent's trace: when the parent (explicit handle or innermost
        open span) carries a remote trace override, spans nested under
        it join that trace too — the propagation that keeps a replica's
        whole request subtree in the router's trace."""
        if parent is not None:
            if isinstance(parent, SpanHandle):
                return parent.span_id, parent.trace_id
            return str(parent), None
        st = self._stack()
        if st:
            return st[-1].span_id, st[-1].trace_id
        return None, None

    def _apply_ctx(self, ctx: Optional[TraceContext], parent,
                   attrs: dict) -> tuple:
        """(parent_id, trace_override) under a remote ``ctx``: the remote
        caller's span becomes the parent (unless an explicit local parent
        was given), the span joins the caller's trace, and the clock-pair
        stamps ride along as attributes."""
        if ctx is None:
            return self._resolve_parent(parent)
        if parent is None:
            parent_id = ctx.span_id
        else:
            parent_id, _ = self._resolve_parent(parent)
        if ctx.send_ts is not None:
            attrs.setdefault("send_ts", float(ctx.send_ts))
        if ctx.recv_ts is not None:
            attrs.setdefault("recv_ts", float(ctx.recv_ts))
        return parent_id, ctx.trace_id

    # ---- distributed-context helpers -------------------------------------
    def next_id(self) -> str:
        """Pre-allocate a span id (for callers that must hand a child its
        parent id before the parent span itself is emitted — the router's
        per-request root, httpc's in-flight fetch span)."""
        return self._next_id()

    def make_ctx(self, parent=None,
                 trace_id: Optional[str] = None) -> Optional[TraceContext]:
        """Context for an outbound hop: this tracer's trace (or the given
        override) parented under ``parent`` (or the innermost open span).
        ``None`` when tracing is off — callers pass it straight through,
        keeping the disabled path allocation-free."""
        if not self.enabled:
            return None
        parent_id, inherited = self._resolve_parent(parent)
        return TraceContext(trace_id or inherited or self.trace_id,
                            parent_id)

    def _emit(self, h: SpanHandle, dur_s: float, extra: dict) -> None:
        if not self.enabled:
            return
        attrs = dict(h.attrs)
        attrs.update(extra)
        try:
            self.registry.event(
                "span",
                name=h.name,
                cat=h.cat,
                span_id=h.span_id,
                trace_id=h.trace_id or self.trace_id,
                parent_id=h.parent_id,
                t0=float(h.t0),
                dur_s=max(float(dur_s), 0.0),
                rank=self._rank,
                thread=threading.current_thread().name,
                **attrs,
            )
        except Exception as e:  # telemetry must never kill the run
            log.warning("span emit failed (%s); continuing", e)

    # ---- explicit begin/end (long-lived roots) ---------------------------
    def begin(self, name: str, cat: str = "host", parent=None,
              ctx: Optional[TraceContext] = None, **attrs: Any) -> SpanHandle:
        """Open a span and push it on this thread's stack (it becomes the
        default parent for spans opened on the same thread until ended).
        With ``ctx`` the span joins a remote caller's trace (see
        :class:`TraceContext`)."""
        parent_id, trace_override = self._apply_ctx(ctx, parent, attrs)
        h = SpanHandle(
            name, cat, self._next_id(), parent_id,
            _now(), attrs, trace_id=trace_override,
        )
        if self.enabled:
            self._stack().append(h)
            h._ann = _annotation(name, attrs)
            h._ann_tid = threading.get_ident()
        return h

    def end(self, h: SpanHandle, **attrs: Any) -> None:
        """Close ``h`` (idempotence is the caller's job) and emit it. Pops
        the handle from this thread's stack if it is there — ends from a
        different thread than the begin simply skip the pop."""
        if h._ann is not None:
            # TraceAnnotation scopes are thread-local: only the opening
            # thread may close one (cross-thread ends just drop it)
            if h._ann_tid == threading.get_ident():
                h._ann.__exit__(None, None, None)
            h._ann = None
        st = self._stack()
        if h in st:
            # close any dangling children too (crash paths)
            while st and st[-1] is not h:
                st.pop()
            if st:
                st.pop()
        h.dur_s = _now() - h.t0
        self._emit(h, h.dur_s, attrs)

    # ---- context-manager form -------------------------------------------
    def span(self, name: str, cat: str = "host", parent=None,
             ctx: Optional[TraceContext] = None, **attrs: Any):
        """``with tracer.span("sample", cat="serve") as h:`` — nests via the
        thread-local stack, annotates any active profiler session."""
        return _SpanCtx(self, name, cat, parent, ctx, attrs)

    # ---- retroactive completion -----------------------------------------
    def complete(self, name: str, dur_s: float, end: Optional[float] = None,
                 t0: Optional[float] = None, cat: str = "host", parent=None,
                 ctx: Optional[TraceContext] = None,
                 span_id: Optional[str] = None, **attrs: Any) -> SpanHandle:
        """Emit a span that ALREADY happened: callers that timed an interval
        themselves (the serve batcher's request marks) hand over
        the duration; ``end`` defaults to now, ``t0`` to ``end - dur_s``.
        ``ctx`` joins the span into a remote caller's trace; ``span_id``
        uses a pre-allocated id (``next_id()``) so children emitted earlier
        can already reference this span as their parent."""
        if t0 is None:
            t0 = (end if end is not None else _now()) - max(dur_s, 0.0)
        parent_id, trace_override = self._apply_ctx(ctx, parent, attrs)
        h = SpanHandle(
            name, cat, span_id or self._next_id(), parent_id,
            float(t0), attrs, trace_id=trace_override,
        )
        self._emit(h, dur_s, {})
        return h


class _SpanCtx:
    __slots__ = ("tracer", "name", "cat", "parent", "ctx", "attrs", "handle")

    def __init__(self, tracer: Tracer, name: str, cat: str, parent, ctx,
                 attrs):
        self.tracer = tracer
        self.name = name
        self.cat = cat
        self.parent = parent
        self.ctx = ctx
        self.attrs = attrs
        self.handle: Optional[SpanHandle] = None

    def __enter__(self) -> SpanHandle:
        self.handle = self.tracer.begin(
            self.name, cat=self.cat, parent=self.parent, ctx=self.ctx,
            **self.attrs
        )
        return self.handle

    def __exit__(self, exc_type, exc, tb) -> None:
        if self.handle is None:
            return
        self.tracer.end(
            self.handle,
            **({"error": type(exc).__name__} if exc_type is not None else {}),
        )
