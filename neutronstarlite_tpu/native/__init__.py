"""ctypes bindings for the native preprocessing runtime.

Builds the shared library on first use if the toolchain is available (one
g++ invocation); everything degrades to the NumPy implementations when the
library can't be built (NTS_NO_NATIVE=1 forces the fallback).

The library is compiled with ``-march=native``, and tools copy checkouts
between machines, so the built file is named for what it was built from
and where: ``libnts_native.<key>.so`` with the key a digest of the source,
the compile command and this host's identity. A library built elsewhere,
or from another source, has another name and is never loaded.
"""

from __future__ import annotations

import ctypes
import glob
import hashlib
import os
import platform
import subprocess
from typing import Optional, Tuple

import numpy as np

from neutronstarlite_tpu.utils.logging import get_logger

log = get_logger("native")

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "graph_native.cpp")
_FLAGS = ["-O3", "-march=native", "-fPIC", "-shared", "-fopenmp", "-std=c++17"]
_lib = None
_tried = False


def _host_identity() -> str:
    """What tells this machine from any other the tree may be copied to:
    the kernel's boot id (new on every boot of every machine; a sealed
    machine made from this one's disk shares its hostname and machine-id),
    with the hostname as the fallback where /proc is not readable."""
    try:
        with open("/proc/sys/kernel/random/boot_id") as fh:
            boot_id = fh.read().strip()
    except OSError:
        boot_id = ""
    return f"{platform.machine()}/{platform.node()}/{boot_id}"


def _so_path() -> str:
    h = hashlib.sha256()
    with open(_SRC, "rb") as fh:
        h.update(fh.read())
    h.update(" ".join([os.environ.get("CXX", "g++")] + _FLAGS).encode())
    h.update(_host_identity().encode())
    return os.path.join(_DIR, f"libnts_native.{h.hexdigest()[:16]}.so")


def _build(so: str) -> bool:
    # build under a private name and rename: concurrent first users (bench
    # workers, replica children) never load a half-written file
    tmp = f"{so}.tmp.{os.getpid()}"
    cmd = [os.environ.get("CXX", "g++"), *_FLAGS, "-o", tmp, _SRC]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=300)
        os.replace(tmp, so)
    except (OSError, subprocess.SubprocessError) as e:
        # toolchain missing / compile error -> fallback
        log.warning("native build failed (%s); using NumPy fallback", e)
        return False
    for old in glob.glob(os.path.join(_DIR, "libnts_native*.so")):
        if old != so:  # another host's or another source's build
            try:
                os.remove(old)
            except OSError:
                pass
    return True


def get_lib() -> Optional[ctypes.CDLL]:
    global _lib, _tried
    if _lib is not None or _tried:
        return _lib
    _tried = True
    if os.environ.get("NTS_NO_NATIVE", "0") == "1":
        return None
    so = _so_path()
    if not os.path.exists(so) and not _build(so):
        return None
    try:
        lib = ctypes.CDLL(so)
    except OSError as e:
        log.warning("failed to load %s: %s", so, e)
        return None

    i32p = np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS")
    i64p = np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")
    u32p = np.ctypeslib.ndpointer(np.uint32, flags="C_CONTIGUOUS")
    f32p = np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS")

    lib.nts_count_degrees.argtypes = [
        u32p, u32p, ctypes.c_int64, ctypes.c_int32, i32p, i32p,
    ]
    lib.nts_build_adjacency.argtypes = [
        u32p, u32p, ctypes.c_int64, ctypes.c_int32, ctypes.c_int,
        i32p, i32p, i64p, i32p, i32p, f32p, i64p, i32p, i32p, f32p,
    ]
    lib.nts_sample_hop.argtypes = [
        i64p, i32p, i64p, ctypes.c_int64, ctypes.c_int32, ctypes.c_uint64,
        i32p, i32p, i32p,
    ]
    lib.nts_sort_by_tile.argtypes = [
        i32p, ctypes.c_int64, ctypes.c_int32, i64p,
    ]
    lib.nts_fill_blocked_level.argtypes = [
        i64p, i64p, i32p, i32p, i64p, ctypes.c_int64, ctypes.c_int64,
        ctypes.c_int32, i32p, f32p, i32p, f32p, i32p,
    ]
    lib.nts_fill_bsp.argtypes = [
        i64p, i64p, i64p, i32p, ctypes.c_int64, i64p, i64p, i32p, f32p,
        ctypes.c_int32, ctypes.c_int32, i32p, f32p, i32p,
    ]
    lib.nts_dedup_remap.argtypes = [
        i64p, ctypes.c_int64, i64p, i32p,
    ]
    lib.nts_dedup_remap.restype = ctypes.c_int64
    lib.nts_native_version.restype = ctypes.c_int
    # libgomp's, reached through the library that links it
    lib.omp_set_num_threads.argtypes = [ctypes.c_int]
    lib.omp_set_num_threads.restype = None
    _lib = lib
    log.info("native runtime loaded (v%d)", lib.nts_native_version())
    return _lib


def available() -> bool:
    return get_lib() is not None


def use_one_thread() -> None:
    """Run this process's native calls on the calling thread only. For
    forked sampler workers: GNU OpenMP's thread pool does not survive a
    fork, so in the child of a parent that has run a parallel region (the
    graph build) the next multi-threaded region never returns."""
    lib = get_lib()
    if lib is not None:
        lib.omp_set_num_threads(1)


def builder_name() -> str:
    """Which host graph builder this process uses: ``native vN`` or
    ``NumPy`` (the fallback after a failed build or NTS_NO_NATIVE=1)."""
    lib = get_lib()
    return f"native v{lib.nts_native_version()}" if lib is not None else "NumPy"


def build_adjacency(
    src: np.ndarray, dst: np.ndarray, v_num: int, weight_mode: int
) -> Tuple[np.ndarray, ...]:
    """Counting-sort CSC+CSR build. Returns (column_offset, csc_src, csc_dst,
    csc_w, row_offset, csr_src, csr_dst, csr_w, out_degree, in_degree).
    Edge order within a vertex's group is unspecified (grouped, dst-/src-
    sorted across groups) — sufficient for the segment ops' sorted promise."""
    lib = get_lib()
    assert lib is not None
    e_num = src.shape[0]
    src = np.ascontiguousarray(src, dtype=np.uint32)
    dst = np.ascontiguousarray(dst, dtype=np.uint32)
    out_degree = np.empty(v_num, np.int32)
    in_degree = np.empty(v_num, np.int32)
    lib.nts_count_degrees(src, dst, e_num, v_num, out_degree, in_degree)
    column_offset = np.zeros(v_num + 1, np.int64)
    np.cumsum(in_degree, out=column_offset[1:])
    row_offset = np.zeros(v_num + 1, np.int64)
    np.cumsum(out_degree, out=row_offset[1:])
    csc_src = np.empty(e_num, np.int32)
    csc_dst = np.empty(e_num, np.int32)
    csc_w = np.empty(e_num, np.float32)
    csr_src = np.empty(e_num, np.int32)
    csr_dst = np.empty(e_num, np.int32)
    csr_w = np.empty(e_num, np.float32)
    lib.nts_build_adjacency(
        src, dst, e_num, v_num, weight_mode, out_degree, in_degree,
        column_offset, csc_src, csc_dst, csc_w,
        row_offset, csr_src, csr_dst, csr_w,
    )
    return (
        column_offset, csc_src, csc_dst, csc_w,
        row_offset, csr_src, csr_dst, csr_w, out_degree, in_degree,
    )


def sort_by_tile(tile_of_edge: np.ndarray, n_tiles: int) -> np.ndarray:
    """Stable counting-sort permutation by source tile (O(E) vs argsort's
    O(E log E)); with dst-grouped input edges the result is (tile, dst)-
    sorted — the blocked ELL build's edge order."""
    lib = get_lib()
    assert lib is not None
    tile = np.ascontiguousarray(tile_of_edge, np.int32)
    order = np.empty(len(tile), np.int64)
    lib.nts_sort_by_tile(tile, len(tile), n_tiles, order)
    return order


def fill_blocked_level(
    row_start: np.ndarray, row_len: np.ndarray, row_tile: np.ndarray,
    row_dst: np.ndarray, row_slot: np.ndarray, n_l: int, K: int,
    src_sorted: np.ndarray, w_sorted: np.ndarray,
    nbr: np.ndarray, wgt: np.ndarray, dstr: np.ndarray,
) -> None:
    """Fill one stacked [T, n_l, K] blocked-ELL level in place (nbr/wgt
    zero-initialized, dstr v_num-filled by the caller)."""
    lib = get_lib()
    assert lib is not None
    lib.nts_fill_blocked_level(
        np.ascontiguousarray(row_start, np.int64),
        np.ascontiguousarray(row_len, np.int64),
        np.ascontiguousarray(row_tile, np.int32),
        np.ascontiguousarray(row_dst, np.int32),
        np.ascontiguousarray(row_slot, np.int64),
        len(row_start), n_l, K,
        np.ascontiguousarray(src_sorted, np.int32),
        np.ascontiguousarray(w_sorted, np.float32),
        nbr, wgt, dstr,
    )


def fill_bsp(
    run_start: np.ndarray, run_len: np.ndarray, row_of_first: np.ndarray,
    run_ldst: np.ndarray, row_block: np.ndarray, row_slot: np.ndarray,
    src_local: np.ndarray, w_sorted: np.ndarray, K: int, R: int,
    nbr: np.ndarray, wgt: np.ndarray, ldst: np.ndarray,
) -> None:
    """Fill the [B, K, R] block-sparse tables in place (ops/bsp_ell.py);
    nbr/wgt/ldst zero-initialized by the caller."""
    lib = get_lib()
    assert lib is not None
    lib.nts_fill_bsp(
        np.ascontiguousarray(run_start, np.int64),
        np.ascontiguousarray(run_len, np.int64),
        np.ascontiguousarray(row_of_first, np.int64),
        np.ascontiguousarray(run_ldst, np.int32),
        len(run_start),
        np.ascontiguousarray(row_block, np.int64),
        np.ascontiguousarray(row_slot, np.int64),
        np.ascontiguousarray(src_local, np.int32),
        np.ascontiguousarray(w_sorted, np.float32),
        K, R, nbr, wgt, ldst,
    )


def sample_hop(
    column_offset: np.ndarray,
    row_indices: np.ndarray,
    dsts: np.ndarray,
    fanout: int,
    seed: int,
) -> Tuple[np.ndarray, np.ndarray]:
    """Fan-out sampling (reservoir or Floyd per degree); returns (src, dst_idx)."""
    lib = get_lib()
    assert lib is not None
    n = len(dsts)
    out_src = np.empty(n * fanout, np.int32)
    out_dst_idx = np.empty(n * fanout, np.int32)
    out_counts = np.empty(n, np.int32)
    lib.nts_sample_hop(
        np.ascontiguousarray(column_offset, np.int64),
        np.ascontiguousarray(row_indices, np.int32),
        np.ascontiguousarray(dsts, np.int64),
        n, fanout, seed, out_src, out_dst_idx, out_counts,
    )
    # compact: keep the first counts[i] entries of each dst's slot
    keep = (np.arange(n * fanout) % fanout) < np.repeat(out_counts, fanout)
    return out_src[keep].astype(np.int64), out_dst_idx[keep].astype(np.int64)


def dedup_remap(ids: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Sorted unique ids + each input's index into them — semantically
    ``uniq = np.unique(ids); local = np.searchsorted(uniq, ids)`` via two
    O(n) hash passes around an m-element sort (sampCSC::postprocessing's
    dedup, coocsc.hpp:62-89). Ids must be NONNEGATIVE (vertex ids): the C
    hash table uses -1 as its empty-slot sentinel."""
    lib = get_lib()
    assert lib is not None
    ids = np.ascontiguousarray(ids, np.int64)
    if len(ids) and ids.min() < 0:
        raise ValueError("dedup_remap requires nonnegative ids (vertex ids)")
    n = len(ids)
    uniq = np.empty(n, np.int64)
    local = np.empty(n, np.int32)
    m = lib.nts_dedup_remap(
        np.ascontiguousarray(ids, np.int64), n, uniq, local
    )
    return uniq[:m], local.astype(np.int64)
