"""Roofline arithmetic every count shares: a need in bytes and
floating-point operations (a configuration's ``needs/<name>.py`` computes
it from shapes alone) over the published peaks (benchmark/peaks.json) is
the least time the chips could take; roofline shares put it over the
device time of the trace.
"""

from __future__ import annotations

from typing import Dict


def least_time(need: Dict[str, float], peaks: dict, chips: int = 1) -> Dict[str, object]:
    """The least time ``chips`` chips could take for ``need``, and which
    peak bounds it."""
    t_hbm = need["bytes"] / (peaks["hbm_bytes_per_s"] * chips)
    t_mxu = need["flops"] / (peaks["bf16_flops_per_s"] * chips)
    return {"seconds": max(t_hbm, t_mxu), "bound": "hbm" if t_hbm >= t_mxu else "flops"}
