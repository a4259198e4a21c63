"""Device time by the program's named scopes, from the trace file.

A ``jax.named_scope`` does not reach the profiler's trace through
``ProfileData``: a device event is named by its HLO instruction's text, and
the scope lives in that instruction's ``op_name`` metadata, which the event
does not carry. The program therefore hands out, for its compiled step, the
table *instruction name -> scope* (``scope_table`` in the record's
``shape``), and this file sums the self time of the ``XLA Ops`` events of
the window by that table. An event whose instruction is not in the table
counts as ``UNSCOPED``; containers (``while``, ``call``, ``conditional``)
run nothing themselves, as in trace_reduce.py.

Pure functions over a trace file and a table; a record without a table (a
program that hands none out, an untraced run) gives None.
"""

from __future__ import annotations

import re
from typing import Dict, Optional

from . import trace_reduce
from .trace_reduce import CONTAINER_OPCODES, DEVICE_PLANE, OPS_LINE, Op

UNSCOPED = "unscoped"
_NAME = re.compile(r"^%?([^\s=]+) = ")


def instruction_name(text: str) -> str:
    """The instruction's full name (``fusion.807``) from its text."""
    m = _NAME.match(text)
    return m.group(1) if m else text[:60]


def seconds_by_scope(trace_file: str, window, table: Dict[str, str],
                     n_devices: Optional[int] = None) -> Dict[str, float]:
    """{scope: self seconds inside ``window``}, mean over the devices, with
    what the table does not name under ``UNSCOPED``."""
    from jax.profiler import ProfileData

    lo, hi = window
    planes = sorted(
        (int(DEVICE_PLANE.match(p.name).group(1)), p)
        for p in ProfileData.from_file(trace_file).planes if DEVICE_PLANE.match(p.name)
    )[:n_devices]
    acc: Dict[str, float] = {}
    unnamed: Dict[str, float] = {}
    for _, plane in planes:
        ops = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for e in line.events:
                a, b = e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9
                _, opcode, shape = trace_reduce.parse_instruction(e.name)
                ops.append(Op(a, b, instruction_name(e.name), opcode, shape))
        for op, self_s in trace_reduce.self_times(ops):
            if op.end <= lo or op.start >= hi or self_s <= 0.0 or op.opcode in CONTAINER_OPCODES:
                continue
            scope = table.get(op.name, UNSCOPED)
            acc[scope] = acc.get(scope, 0.0) + self_s
            if scope == UNSCOPED:
                unnamed[op.label] = unnamed.get(op.label, 0.0) + self_s
    if unnamed:
        from . import runtime

        runtime.log("longest operations the scope table does not name: " + ", ".join(
            f"{label} {s:.4f}" for label, s in sorted(unnamed.items(), key=lambda kv: -kv[1])[:8]))
    return {k: v / max(len(planes), 1) for k, v in acc.items()}


def of_run(ctx, record) -> Optional[Dict[str, float]]:
    """``seconds_by_scope`` of this run's traced window, computed once;
    None where the run was not traced or the program handed out no table."""
    table = record.get("shape", {}).get("scope_table")
    red = ctx.reduction
    if not table or red is None or not ctx.trace_file:
        return None
    if "_seconds_by_scope" not in record:
        from . import runtime

        by_scope = seconds_by_scope(ctx.trace_file, red.window, table, ctx.chips)
        total = sum(by_scope.values())
        runtime.log("device seconds by scope over the window: " + ", ".join(
            f"{k} {v:.4f}" for k, v in sorted(by_scope.items(), key=lambda kv: -kv[1])
        ) + (f"; the table names {100.0 * (1.0 - by_scope.get(UNSCOPED, 0.0) / total):.1f}% "
             "of the device time" if total > 0 else ""))
        record["_seconds_by_scope"] = by_scope
    return record["_seconds_by_scope"]
