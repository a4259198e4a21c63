"""The program's finished span records, read in process.

The program keeps every record its registry emits in a bounded ring
(``obs/flight.py``, 2,048 records) and holds the newest ring for readers
that come after the trainer is gone. This is the one import of the program
outside ``harness/program.py``. A program without the accessor (an older
commit), a ring that is switched off (``NTS_FLIGHT=0``) and a ring that has
wrapped past the run's first record all read as None: nothing is buffered
here to make up for them.
"""

from __future__ import annotations

from typing import Dict, List, Optional

FUNNEL_CAT = "phase"
EPOCH = "epoch"


def span_records() -> Optional[List[Dict]]:
    """The run's span records, oldest first, or None (see above)."""
    try:
        from neutronstarlite_tpu.obs.flight import recent_records
    except ImportError:
        return None
    if not recent_records("run_start"):  # no ring, or the start has left it
        return None
    return recent_records("span")


def first(records: List[Dict], name: str) -> Optional[Dict]:
    return next((r for r in records if r["name"] == name), None)


def seconds_of(names) -> Optional[float]:
    """Sum of the durations of the spans with these names; None where the
    program recorded none."""
    found = [r["dur_s"] for r in span_records() or [] if r["name"] in names]
    return float(sum(found)) if found else None


def first_seconds(name: str) -> Optional[float]:
    """Duration of the run's first span of this name (the warm-up
    ``run()``'s, for a span the run loop opens), or None."""
    record = first(span_records() or [], name)
    return None if record is None else float(record["dur_s"])


def funnel_spanned_s() -> Optional[float]:
    """Seconds under the funnel's top-level phase spans that ended before
    the first epoch began (a phase opened inside another phase is in its
    parent's time already); None where the program recorded no phase."""
    records = span_records() or []
    phases = [r for r in records if r["cat"] == FUNNEL_CAT]
    first_epoch = first(records, EPOCH)
    if not phases or first_epoch is None:
        return None
    phase_ids = {r["span_id"] for r in phases}
    return float(sum(
        r["dur_s"] for r in phases
        if r["parent_id"] not in phase_ids
        and r["t0"] + r["dur_s"] <= first_epoch["t0"]
    ))
