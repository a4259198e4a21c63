"""BENCHMARK.json and the data files it names.

A cell (one entry of ``workloads``) names a configuration and a traffic mix;
each of those, and each per-layer metric, is a file of its own found by its
name, so a later PR adds cells, configurations, mixes and metrics as new
files and new entries and edits nothing that is here.
"""

from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH_DIR)
CACHE_DIR = os.path.join(BENCH_DIR, ".cache")


class SpecError(Exception):
    pass


def load_benchmark() -> dict:
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        return json.load(fh)


def _by_name(entries: list, name: str, what: str) -> dict:
    for entry in entries:
        if entry["name"] == name:
            return entry
    known = ", ".join(e["name"] for e in entries)
    raise SpecError(f"no {what} named {name!r} in BENCHMARK.json (known: {known})")


def load_cell(bench: dict, workload: str) -> dict:
    """The cell's entry with its configuration and traffic files read in."""
    cell = dict(_by_name(bench["workloads"], workload, "workload"))
    entry = _by_name(bench["configs"], cell["config"], "config")
    with open(os.path.join(REPO, entry["file"])) as fh:
        cell["config_data"] = json.load(fh)
    with open(os.path.join(BENCH_DIR, "traffic", cell["traffic"] + ".json")) as fh:
        cell["traffic_data"] = json.load(fh)
    return cell


def layer_reader(name: str):
    """benchmark/layer_metrics/<name>.py: ``read(ctx, record)`` returns the
    metric's value, or None where there is nothing to read."""
    path = os.path.join(BENCH_DIR, "layer_metrics", name + ".py")
    module_spec = importlib.util.spec_from_file_location(f"layer_metrics.{name}", path)
    module = importlib.util.module_from_spec(module_spec)
    module_spec.loader.exec_module(module)
    return module.read


def metrics_for(bench: dict, section: str, workload: str) -> list:
    """The metrics of ``end_to_end`` or ``per_layer`` that this cell reports
    (one that lists ``workloads`` exists only in those cells)."""
    return [
        m for m in bench[section]
        if "workloads" not in m or workload in m["workloads"]
    ]


def load_peaks(device_kind: str) -> dict:
    """Published peaks of the device, keyed by ``device_kind``. A device
    that is not in the table is an error, not a default."""
    with open(os.path.join(BENCH_DIR, "peaks.json")) as fh:
        table = json.load(fh)["devices"]
    if device_kind not in table:
        raise SpecError(
            f"device_kind {device_kind!r} is not in benchmark/peaks.json "
            f"(known: {sorted(table)}); add its published peaks with their source"
        )
    return table[device_kind]
