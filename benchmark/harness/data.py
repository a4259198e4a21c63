"""The graph of a configuration: its generator and its cache on disk.

The graph's topology belongs to the configuration (its file gives the
generator's parameters and seed): it is the data set a deployment trains
and serves on, run after run, and the program's compiled shapes (ELL bucket
widths, shard sizes, dedup capacities) are functions of its degree
sequence, so a topology drawn from ``--seed`` would compile anew in every
run. What comes from ``--seed`` (features, labels, the split, the weights)
is made by the configuration's ``inputs`` module.

``power_law_edges`` is a copy of the program's
``graph/synthetic.py:synthetic_power_law_graph`` (same draws for the same
seed) with the exponent and symmetrisation as parameters; the yardstick
keeps its own so that a later change to the program cannot move it.
"""

from __future__ import annotations

import hashlib
import json
import os
import shutil
from typing import Dict, Tuple

import numpy as np

GENERATOR_VERSION = 1  # bump when the draws change: the cache key holds it


def power_law_edges(
    vertices: int, edges: int, seed: int, exponent: float = 3.0,
    self_loops: bool = True, symmetric: bool = False,
) -> Tuple[np.ndarray, np.ndarray]:
    """(src, dst) uint32. ``edges`` counts directed edges including the
    self loops; with ``symmetric`` every drawn pair is stored in both
    directions (an undirected data set given as ``edges`` directed ones).
    Endpoints are drawn as ``V * u**exponent`` (mass toward low ids: hubs)
    through a random permutation of the ids."""
    rng = np.random.default_rng(seed)
    n_rand = edges - (vertices if self_loops else 0)
    if symmetric:
        if n_rand % 2:
            raise ValueError("symmetric graph needs an even number of non-loop edges")
        n_rand //= 2
    if n_rand < 0:
        raise ValueError("edges smaller than the self-loop count")
    src = (vertices * rng.random(n_rand) ** exponent).astype(np.uint32)
    dst = (vertices * rng.random(n_rand) ** exponent).astype(np.uint32)
    perm = rng.permutation(vertices).astype(np.uint32)
    src, dst = perm[src], perm[dst]
    parts_s, parts_d = [src], [dst]
    if symmetric:
        parts_s.append(dst)
        parts_d.append(src)
    if self_loops:
        loops = np.arange(vertices, dtype=np.uint32)
        parts_s.append(loops)
        parts_d.append(loops)
    return np.concatenate(parts_s), np.concatenate(parts_d)


def graph_params(config: dict, rehearse: bool) -> dict:
    """The generator's parameters; a rehearsal swaps in the tiny sizes."""
    params = dict(config["graph"])
    if rehearse:
        params.update(config["rehearse"]["graph"])
    return params


def graph_key(params: dict) -> str:
    blob = json.dumps({"v": GENERATOR_VERSION, **params}, sort_keys=True)
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


def make_edges(params: dict) -> Tuple[np.ndarray, np.ndarray]:
    if params["generator"] != "power_law":
        raise ValueError(f"unknown graph generator {params['generator']!r}")
    return power_law_edges(**{k: v for k, v in params.items() if k != "generator"})


def sorted_edges(params: dict, cache_root: str, by: str) -> Tuple[np.ndarray, np.ndarray]:
    """(src, dst) of the configuration's graph, sorted by ``by`` ("dst" or
    "src"), kept on disk beside the program's host graph: what the plain
    reference starts from, so that nothing the program builds from the
    edge list reaches the comparison."""

    def build() -> Dict[str, np.ndarray]:
        src, dst = make_edges(params)
        order = np.argsort(dst if by == "dst" else src, kind="stable")
        return {"src": src[order], "dst": dst[order]}

    cache_dir = os.path.join(cache_root, "graphs", f"{graph_key(params)}-edges-by-{by}")
    arrays, _ = load_or_build(cache_dir, ("src", "dst"), build)
    return arrays["src"], arrays["dst"]


def load_or_build(cache_dir: str, fields: Tuple[str, ...], build) -> Tuple[Dict[str, np.ndarray], bool]:
    """Arrays of ``build()`` kept as .npy files under ``cache_dir``. Returns
    (arrays, was_cached). The directory appears by one rename, so a run that
    was cut while writing leaves nothing that looks complete."""
    if os.path.isdir(cache_dir):
        return {f: np.load(os.path.join(cache_dir, f + ".npy")) for f in fields}, True
    arrays = build()
    tmp = f"{cache_dir}.writing.{os.getpid()}"
    shutil.rmtree(tmp, ignore_errors=True)
    os.makedirs(tmp)
    for f in fields:
        np.save(os.path.join(tmp, f + ".npy"), arrays[f])
    try:
        os.rename(tmp, cache_dir)
    except OSError:  # another run of the same graph finished first
        shutil.rmtree(tmp, ignore_errors=True)
    return arrays, False
