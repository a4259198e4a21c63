"""Test rig: force CPU platform with 8 virtual devices, and a persistent
compile cache for the run.

This is the analog of the reference's single-machine multi-slot mpiexec rig
(run_nts.sh, README "use one slot, except for debugging") — multi-"chip"
behavior is exercised without TPU hardware via
--xla_force_host_platform_device_count, per SURVEY.md section 4.
Must run before the first jax import in the test process.
"""

import os
import shutil
import tempfile

os.environ["JAX_PLATFORMS"] = "cpu"
# JAX's persistent compile cache, one directory per test worker, outside the
# checkout (its .jax_cache holds the TPU runs' entries, and copies of the
# tree carry it).
# Set through the environment so that every process a test starts reads and
# writes the worker's directory, with every compile cached as the entry
# points cache it (utils/platform.py). A worker runs one test at a time and
# waits for the processes it starts, so a directory has one writer at a time:
# JAX writes an entry without a lock. ``--dist loadfile`` keeps a file's tests
# on one worker, so a file's cell rehearsals share what they compile.
if os.environ.get("PYTEST_XDIST_WORKER"):
    _CACHE_ROOT = os.path.join(
        tempfile.gettempdir(), "nts-tier1-jax-cache-" + os.environ["PYTEST_XDIST_TESTRUNUID"])
    COMPILE_CACHE_DIR = os.path.join(_CACHE_ROOT, os.environ["PYTEST_XDIST_WORKER"])
else:
    _CACHE_ROOT = COMPILE_CACHE_DIR = tempfile.mkdtemp(prefix="nts-tier1-jax-cache-")
os.environ["JAX_COMPILATION_CACHE_DIR"] = COMPILE_CACHE_DIR
os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

# pytest plugins may import jax before this file runs, and jax reads
# JAX_PLATFORMS and the cache's settings only at import; pin the config as well.
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_unconfigure(config):
    """The worker's cache goes with the run; the last worker out removes
    the run's directory."""
    shutil.rmtree(COMPILE_CACHE_DIR, ignore_errors=True)
    try:
        os.rmdir(_CACHE_ROOT)
    except OSError:  # another worker's directory is still there, or gone already
        pass


@pytest.fixture
def rng():
    return np.random.default_rng(1234)


def tiny_graph(rng, v_num=23, e_num=101, weight="gcn_norm", self_loops=True):
    """Small random multigraph + its dense adjacency for golden checks."""
    from neutronstarlite_tpu.graph.storage import build_graph

    src = rng.integers(0, v_num, size=e_num, dtype=np.uint32)
    dst = rng.integers(0, v_num, size=e_num, dtype=np.uint32)
    if self_loops:
        loops = np.arange(v_num, dtype=np.uint32)
        src = np.concatenate([src, loops])
        dst = np.concatenate([dst, loops])
    g = build_graph(src, dst, v_num, weight=weight)
    # dense [V, V] weight matrix A with A[dst, src] = sum of edge weights
    dense = np.zeros((v_num, v_num), dtype=np.float64)
    # rebuild weights in original edge order for the dense reference
    w = {
        "gcn_norm": None,
        "ones": np.ones(len(src), dtype=np.float64),
    }[weight if weight == "ones" else "gcn_norm"]
    if w is None:
        from neutronstarlite_tpu.graph.storage import gcn_norm_weights

        w = gcn_norm_weights(src, dst, g.out_degree, g.in_degree).astype(np.float64)
    np.add.at(dense, (dst.astype(np.int64), src.astype(np.int64)), w)
    return g, dense
