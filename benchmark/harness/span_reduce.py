"""The program's own spans in the profiler's trace, against the device.

The program's live spans are ``TraceAnnotation`` events named ``nts:<span>``
on the host thread that ran them, on the clock of the device lines (to about
a millisecond). A run loop's thread carries, per epoch, one ``nts:epoch``
event that holds its stage events (``nts:step_dispatch``,
``nts:step_device``, ``nts:loss_fetch``, ...), all under ``nts:run``. These
are pure functions over a ``trace_reduce.Reduction``: its ``host_lines``,
its ``window`` and the busy intervals of its devices. A trace of a program
that emits no such event gives None everywhere.

Definitions:

- an epoch of the window is located by its ``nts:step_dispatch`` event,
  which opens at the top of the iteration; epochs are numbered by start
  order inside the window. Its ``nts:epoch`` event is the one that holds
  the dispatch; the window's last epoch has none, because the traced
  window ends (the profiler stops) inside it, and an annotation is
  written when it closes.
- a moment belongs to the innermost ``nts:`` event open on the loop's
  thread: an event's own time is its interval less the events nested in
  it. Idle time is split over the events by intersection with their own
  time, so a gap that straddles two stages is shared between them.
- ``nts:run`` and ``nts:epoch`` hold other spans and name no work of
  their own: idle time that belongs to them, or to no event at all, is
  unspanned.
"""

from __future__ import annotations

import bisect
from typing import Dict, List, Optional, Tuple

from . import stats
from .trace_reduce import Interval, Reduction, clip, subtract, total, union

Event = Tuple[float, float, str]  # (start, end, name) as host_lines has them

PREFIX = "nts:"
EPOCH = PREFIX + "epoch"
DISPATCH = PREFIX + "step_dispatch"
DEVICE_WAIT = PREFIX + "step_device"
HOLDERS = (PREFIX + "run", EPOCH)
NO_SPAN = "no span"


def loop_events(red: Reduction) -> List[Event]:
    """The ``nts:`` events of the host thread that ran the epochs (the one
    with a ``nts:step_dispatch``), in start order; [] without one."""
    for events in red.host_lines.values():
        if any(name == DISPATCH for _, _, name in events):
            return sorted(e for e in events if e[2].startswith(PREFIX))
    return []


def window_epochs(red: Reduction) -> List[Dict[str, Optional[Event]]]:
    """One entry per epoch that started inside the window, in start order:
    its ``dispatch`` event, the ``device`` wait that followed it, the
    ``epoch`` event that holds them (None for an epoch the window cut),
    and its ``start`` and ``end`` (for a cut epoch, the end of the epoch
    before it and the window's end)."""
    lo, hi = red.window
    events = loop_events(red)
    dispatches = [e for e in events if e[2] == DISPATCH and lo <= e[0] < hi]
    out = []
    for i, d in enumerate(dispatches):
        until = dispatches[i + 1][0] if i + 1 < len(dispatches) else hi
        waits = [e for e in events if e[2] == DEVICE_WAIT and d[1] <= e[0] < until]
        holders = [e for e in events if e[2] == EPOCH and e[0] <= d[0] and d[1] <= e[1]]
        epoch = holders[-1] if holders else None
        # a cut epoch opened where the whole one before it closed (its
        # stages ahead of the dispatch, the key, are its own)
        cut_start = out[-1]["end"] if out and out[-1]["epoch"] else d[0]
        out.append({
            "dispatch": d,
            "device": waits[0] if waits else None,
            "epoch": epoch,
            "start": epoch[0] if epoch else cut_start,
            "end": epoch[1] if epoch else hi,
        })
    return out


def epoch_host_tail_ms(red: Reduction) -> Optional[float]:
    """Median over the window's whole epochs of the epoch's length less its
    ``step_dispatch`` and ``step_device``: what the host does around the
    step (loss fetch, records, logits copy, accuracy, checkpoint hook)."""
    tails = [
        (e["epoch"][1] - e["epoch"][0]) - (e["dispatch"][1] - e["dispatch"][0])
        - (e["device"][1] - e["device"][0])
        for e in window_epochs(red) if e["epoch"] and e["device"]
    ]
    return stats.median(tails) * 1000.0 if tails else None


def step_launch_ms_max(red: Reduction) -> Optional[float]:
    """Longest, over the window's epochs, from the start of
    ``step_dispatch`` to the start of the first operation the first device
    starts after it (an operation still running when the dispatch opens,
    the epoch's key, is not the step's); 0 for an epoch whose dispatch
    the device works through from end to end."""
    busy = red.devices[0].busy
    starts = [a for a, _ in busy]
    launches = []
    for e in window_epochs(red):
        t, done = e["dispatch"][0], e["dispatch"][1]
        i = bisect.bisect_right(starts, t)
        if i > 0 and busy[i - 1][1] >= done:
            launches.append(0.0)
        elif i < len(busy):
            launches.append(busy[i][0] - t)
    return max(launches) * 1000.0 if launches else None


def steady_idle_share(red: Reduction) -> Optional[float]:
    """Idle share (%, mean over the devices) of the window from the start
    of its second epoch to its end: the window without the first epoch's
    launch."""
    epochs = window_epochs(red)
    if len(epochs) < 2:
        return None
    lo, hi = epochs[1]["start"], red.window[1]
    busy_s = sum(total(clip(d.busy, lo, hi)) for d in red.devices) / len(red.devices)
    return 100.0 * (1.0 - busy_s / (hi - lo))


def own_time(events: List[Event]) -> List[Tuple[Event, List[Interval]]]:
    """Each event with its own time: its interval less the events nested
    inside it (events of one thread nest or are disjoint)."""
    out = []
    for e in events:
        nested = union(
            (o[0], o[1]) for o in events
            if e[0] <= o[0] and o[1] <= e[1] and o[1] - o[0] < e[1] - e[0]
        )
        out.append((e, subtract([(e[0], e[1])], nested)))
    return out


def idle_by_span(red: Reduction) -> Optional[List[List]]:
    """[[label, idle seconds]] of the first device's idle time in the
    window, by the innermost ``nts:`` event of the loop's thread it falls
    under, longest first. A label is the event's name, with ``e<k>/`` in
    front for an event inside the window's k-th epoch; idle time under no
    event is ``NO_SPAN``. None for a trace without ``nts:`` events."""
    events = loop_events(red)
    if not events:
        return None
    gaps = subtract([red.window], red.devices[0].busy)
    epochs = window_epochs(red)
    starts = [e["start"] for e in epochs]
    acc: Dict[str, float] = {}
    spanned = 0.0
    for (a, _, name), own in own_time(events):
        idle = sum(total(clip(gaps, lo, hi)) for lo, hi in own)
        if idle <= 0.0:
            continue
        k = bisect.bisect_right(starts, a) - 1
        label = f"e{k}/{name}" if k >= 0 and a < epochs[k]["end"] else name
        acc[label] = acc.get(label, 0.0) + idle
        spanned += idle
    rest = total(gaps) - spanned
    if rest > 1e-9:
        acc[NO_SPAN] = rest
    return [[label, s] for label, s in sorted(acc.items(), key=lambda kv: -kv[1])]


def idle_unspanned_share(split: Optional[List[List]]) -> Optional[float]:
    """Share (%) of the idle time of an ``idle_by_span`` split that falls
    under no stage span: under ``nts:run`` or ``nts:epoch`` alone, or under
    no ``nts:`` event. What the instrumentation does not cover."""
    if split is None:
        return None
    idle = sum(s for _, s in split)
    if idle <= 0.0:
        return 0.0
    unspanned = sum(
        s for label, s in split
        if label == NO_SPAN or label.split("/")[-1] in HOLDERS
    )
    return 100.0 * unspanned / idle
