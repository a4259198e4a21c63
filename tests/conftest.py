"""Test rig: force CPU platform with 8 virtual devices.

This is the analog of the reference's single-machine multi-slot mpiexec rig
(run_nts.sh, README "use one slot, except for debugging") — multi-"chip"
behavior is exercised without TPU hardware via
--xla_force_host_platform_device_count, per SURVEY.md section 4.
Must run before the first jax import in the test process.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
# The suite compiles on the CPU only; entry points would otherwise fill the
# checkout's .jax_cache with CPU executables (the chip tool copies the tree).
# Set through the environment so test subprocesses inherit it.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

# pytest plugins may import jax before this file runs, and jax reads
# JAX_PLATFORMS only at import; pin the config as well.
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_compilation_cache", False)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


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
