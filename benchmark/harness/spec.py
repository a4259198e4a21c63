"""BENCHMARK.json and the files it names.

A cell (one entry of ``workloads``) names a configuration and a traffic mix.
Whatever belongs to one configuration, one mix, one kind of traffic or one
per-layer metric is a file of its own found by its name, so a later PR adds
cells of a kind that is not here yet as new files and new entries and edits
nothing that is here:

    configs/<config>.json         the configuration, as it is run
    traffic/<mix>.json            a mix's parameters; its ``kind`` names
    kinds/<kind>.py               ``run_cell(ctx)``: how traffic of that kind is driven
    inputs/<name>.py              ``build(ctx)`` -> (inputs, trainer), ``shape(inputs, trainer)``
    checks/<name>.py              ``check(ctx, inputs, trainer, record)`` -> (errors, faults)
    needs/<name>.py               ``epoch_need(shape)`` and, with an exchange,
                                  ``wire_rows_per_device(shape)``
    reference/<name>.py           the plain reference a check compares with
    layer_metrics/<metric>.py     ``read(ctx, record)``

A configuration names its ``inputs``, ``check`` and ``need`` modules under
those keys, and its ``reference``; one that does not (the three that were
here before the keys were) gets ``CONFIG_MODULES``' defaults.
"""

from __future__ import annotations

import functools
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


@functools.lru_cache(maxsize=None)
def named_module(directory: str, name: str):
    """benchmark/<directory>/<name>.py, loaded once, by its path: a name
    here never meets a module of the same name elsewhere on ``sys.path``."""
    path = os.path.join(BENCH_DIR, directory, name + ".py")
    if not os.path.isfile(path):
        raise SpecError(f"no {path}: {directory}/ has no module named {name!r}")
    module_spec = importlib.util.spec_from_file_location(f"{directory}.{name}", path)
    module = importlib.util.module_from_spec(module_spec)
    module_spec.loader.exec_module(module)
    return module


# key of a configuration -> (directory, the name a configuration without the key gets)
CONFIG_MODULES = {
    "inputs": ("inputs", "vertex_graph"),
    "check": ("checks", "vertex_graph"),
    "need": ("needs", "gcn"),
}


def config_module(config: dict, key: str):
    """The module the configuration names under ``inputs``, ``check`` or
    ``need``."""
    directory, default = CONFIG_MODULES[key]
    return named_module(directory, config.get(key, default))


def traffic_kind(kind: str):
    """benchmark/kinds/<kind>.py's ``run_cell(ctx)``, which returns the
    cell's record."""
    return named_module("kinds", kind).run_cell


def layer_reader(name: str):
    """benchmark/layer_metrics/<name>.py: ``read(ctx, record)`` returns the
    metric's value, or None where there is nothing to read."""
    return named_module("layer_metrics", name).read


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
