"""From a profiler trace (.xplane.pb) to device busy time, idle gaps, and
time by operation, with ``jax.profiler.ProfileData`` and nothing else.

What a TPU trace holds (looked at by hand on a v5e, PR 22): one plane per
chip named ``/device:TPU:<n>`` with the lines ``XLA Modules`` (one event
per program run), ``XLA Ops`` (one event per executed HLO instruction,
named by the instruction's whole text; a ``while`` or ``call`` event spans
the events of its body) and ``Async XLA Ops`` (the start-to-done span of
asynchronous copies and collectives); and a plane ``/host:CPU`` with one
line per host thread, which carries the Python tracer's events
(``$file.py:line function``) and ``TraceAnnotation`` spans. Times are
nanoseconds on one clock (device and host lines agree to about a
millisecond).

Definitions:

- busy: the union of the ``XLA Ops`` intervals of a device, clipped to the
  window, over leaf operations only: a ``while``, ``call`` or
  ``conditional`` event is the extent of its body and runs nothing itself,
  so the pauses between the operations of a scanned epoch count as idle.
  Idle share is 1 - busy / window.
- an operation's time is its self time: its interval less what events
  nested inside it cover. Times are summed by group (the instruction's
  name without its numeric suffix, its opcode and its result shape); the
  groups partition the busy time.
- a collective is an instruction whose opcode starts with one of
  ``COLLECTIVE_OPCODES`` on either line. Its exposed time is the part of
  the union of collective intervals in which no other leaf operation runs
  on that device.
- an idle gap is a maximal interval of the window in which the device runs
  nothing. It is named by what the host was doing: the innermost host
  event that covers the gap's midpoint, on the thread that logged the most
  events inside the gap.
"""

from __future__ import annotations

import bisect
import dataclasses
import functools
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
PYTHON_EVENT = "$"  # the Python tracer names its events "$file.py:line function"
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
COLLECTIVE_OPCODES = (
    "all-gather", "all-reduce", "reduce-scatter", "collective-permute",
    "all-to-all", "collective-broadcast", "ragged-all-to-all",
)
NAMED_GAPS = 200  # naming a gap scans the host's events: bound the work
CONTAINER_OPCODES = ("while", "call", "conditional")  # span their bodies' events
_INSTRUCTION = re.compile(r"^%?(?P<name>[^\s=]+) = (?P<rest>.*)$", re.S)
_OPCODE = re.compile(r" ([a-z][\w\-]*)\(")
_SUFFIX = re.compile(r"(\.\d+)+$")


@dataclasses.dataclass(frozen=True)
class Op:
    start: float
    end: float
    name: str  # instruction name without its numeric suffix
    opcode: str
    shape: str  # result shape, layout dropped

    @property
    def label(self) -> str:
        return f"{self.opcode} {self.name} {self.shape}"


@functools.lru_cache(maxsize=None)  # a scan repeats its instructions every iteration
def parse_instruction(text: str) -> Tuple[str, str, str]:
    """(name stem, opcode, result shape) of an HLO instruction's text, e.g.
    ``%fusion.5 = bf16[320000,128]{1,0:T(8,128)} fusion(...), kind=kLoop``
    -> ("fusion", "fusion", "bf16[320000,128]"). A text that is not an
    instruction is its own name with the opcode "unknown"."""
    m = _INSTRUCTION.match(text)
    if not m:
        return text[:60], "unknown", ""
    name = _SUFFIX.sub("", m.group("name"))
    rest = m.group("rest")
    op = _OPCODE.search(" " + rest)
    if not op:
        return name, "unknown", ""
    shape = rest[: max(op.start() - 1, 0)].strip()
    shape = re.sub(r"\{[^{}]*\}", "", shape)  # layouts
    if len(shape) > 48:
        shape = shape[:45] + "..."
    return name, op.group(1), shape


def is_collective(opcode: str) -> bool:
    return opcode.startswith(COLLECTIVE_OPCODES)


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Disjoint, sorted intervals covering the same points."""
    out: List[List[float]] = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def total(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def clip(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals if min(b, hi) > max(a, lo)]


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Points of the disjoint sorted ``a`` not covered by the disjoint
    sorted ``b``."""
    out = []
    j = 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def self_times(ops: Sequence[Op]) -> List[Tuple[Op, float]]:
    """Each op with its self time: its length less the union of the ops
    nested inside it. Events on one device line nest or are disjoint."""
    order = sorted(ops, key=lambda o: (o.start, -(o.end - o.start)))
    out: List[List] = []
    stack: List[int] = []  # indices into out of the open ancestors
    for op in order:
        while stack and out[stack[-1]][0].end <= op.start:
            stack.pop()
        if stack:
            parent = out[stack[-1]]
            parent[1] -= min(op.end, parent[0].end) - op.start
        out.append([op, op.end - op.start])
        stack.append(len(out) - 1)
    return [(op, max(t, 0.0)) for op, t in out]


@dataclasses.dataclass
class DeviceReduction:
    ordinal: int
    busy: List[Interval]  # clipped to the window
    by_group: Dict[str, float]  # label -> self seconds inside the window
    collective: List[Interval]
    collective_exposed_s: float

    @property
    def busy_s(self) -> float:
        return total(self.busy)

    @property
    def collective_s(self) -> float:
        return total(self.collective)


@dataclasses.dataclass
class Reduction:
    window: Interval  # seconds on the trace's clock
    devices: List[DeviceReduction]
    host_lines: Dict[str, List[Tuple[float, float, str]]]

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def busy_s(self) -> float:
        """Mean over the devices used."""
        return sum(d.busy_s for d in self.devices) / len(self.devices)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def share(self, seconds_of) -> Optional[float]:
        """Mean over devices of ``seconds_of(device) / device.busy_s``."""
        shares = [seconds_of(d) / d.busy_s for d in self.devices if d.busy_s > 0]
        return sum(shares) / len(shares) if shares else None

    def busy_percent(self, seconds_of) -> Optional[float]:
        share = self.share(seconds_of)
        return None if share is None else 100.0 * share

    def top_ops(self, n: int = 10) -> List[List]:
        """[[label, seconds]] summed over devices, longest first."""
        acc: Dict[str, float] = {}
        for d in self.devices:
            for label, s in d.by_group.items():
                acc[label] = acc.get(label, 0.0) + s
        ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
        return [[label, s] for label, s in ranked]

    def idle_gaps(self, n: int = 10) -> List[List]:
        """[[what the host was doing, idle seconds]] over the gaps of the
        first device, summed by name, longest first. The ``NAMED_GAPS``
        longest gaps are named one by one; the many short ones between
        two operations are summed under one name."""
        gaps = sorted(subtract([self.window], self.devices[0].busy),
                      key=lambda g: g[0] - g[1])
        acc: Dict[str, float] = {}
        rest = total(gaps[NAMED_GAPS:])
        if rest > 0:
            acc["shorter gaps between operations"] = rest
        for lo, hi in gaps[:NAMED_GAPS]:
            name = host_activity(self.host_lines, lo, hi)
            acc[name] = acc.get(name, 0.0) + (hi - lo)
        ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
        return [[name, s] for name, s in ranked]


def host_activity(host_lines: Dict[str, List[Tuple[float, float, str]]],
                  lo: float, hi: float) -> str:
    """Name of the innermost host event covering the midpoint of [lo, hi],
    on the Python thread with the most events that start inside it (a
    thread that waits logs nothing; the one at work logs much). A trace
    taken without the Python tracer is read over all host threads."""
    python_lines = {
        line: events for line, events in host_lines.items()
        if any(name.startswith(PYTHON_EVENT) for _, _, name in events)
    }
    mid = (lo + hi) / 2.0
    ranked = []
    for line, events in (python_lines or host_lines).items():  # no Python tracer: any thread
        starts = [e[0] for e in events]
        count = bisect.bisect_left(starts, hi) - bisect.bisect_left(starts, lo)
        covering = [e for e in events if e[0] <= mid < e[1]]
        if covering:
            innermost = min(covering, key=lambda e: e[1] - e[0])
            ranked.append((count, innermost[2]))
    if not ranked:
        return "no host event"
    return max(ranked, key=lambda t: t[0])[1]


def _events(line) -> List[Tuple[float, float, str]]:
    return [
        (e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9, e.name)
        for e in line.events
    ]


def reduce_profile(profile, window_name: str, n_devices: Optional[int] = None) -> Reduction:
    """Reduce a ``ProfileData``. The window is the host annotation
    ``window_name``; without one it is the extent of the device events."""
    device_planes = []
    host_lines: Dict[str, List[Tuple[float, float, str]]] = {}
    window: Optional[Interval] = None
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            device_planes.append((int(m.group(1)), plane))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                events = sorted(_events(line))
                if events:
                    host_lines[line.name] = events
                for a, b, name in events:
                    if name == window_name:
                        window = (a, b)
    if not device_planes:
        raise ValueError("the trace holds no /device:TPU:<n> plane")
    device_planes.sort(key=lambda t: t[0])
    if n_devices is not None:
        device_planes = device_planes[:n_devices]

    per_device = []
    for ordinal, plane in device_planes:
        ops: List[Op] = []
        async_ops: List[Op] = []
        for line in plane.lines:
            if line.name not in (OPS_LINE, ASYNC_LINE):
                continue
            target = ops if line.name == OPS_LINE else async_ops
            for a, b, text in _events(line):
                name, opcode, shape = parse_instruction(text)
                target.append(Op(a, b, name, opcode, shape))
        per_device.append((ordinal, ops, async_ops))
    if window is None:
        every = [o for _, ops, _ in per_device for o in ops]
        if not every:
            raise ValueError("no operation ran on the device in the trace")
        window = (min(o.start for o in every), max(o.end for o in every))

    devices = [
        reduce_device(ordinal, ops, async_ops, window)
        for ordinal, ops, async_ops in per_device
    ]
    return Reduction(window=window, devices=devices, host_lines=host_lines)


def reduce_device(ordinal: int, ops: Sequence[Op], async_ops: Sequence[Op],
                  window: Interval) -> DeviceReduction:
    """One device's ``XLA Ops`` and ``Async XLA Ops`` events, reduced over
    the window."""
    lo, hi = window
    busy = clip(union(
        (o.start, o.end) for o in ops if o.opcode not in CONTAINER_OPCODES
    ), lo, hi)
    by_group: Dict[str, float] = {}
    compute: List[Interval] = []
    for op, self_s in self_times(ops):
        if (op.end <= lo or op.start >= hi or self_s <= 0.0
                or op.opcode in CONTAINER_OPCODES):
            continue
        by_group[op.label] = by_group.get(op.label, 0.0) + self_s
        if not is_collective(op.opcode):
            compute.append((op.start, op.end))
    coll = clip(union(
        (o.start, o.end) for o in list(ops) + list(async_ops) if is_collective(o.opcode)
    ), lo, hi)
    exposed = subtract(coll, clip(union(compute), lo, hi))
    return DeviceReduction(
        ordinal=ordinal, busy=busy, by_group=by_group,
        collective=coll, collective_exposed_s=total(exposed),
    )


def reduce_file(path: str, window_name: str, n_devices: Optional[int] = None) -> Reduction:
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(path), window_name, n_devices)
