"""Device mesh construction — the TPU analog of the MPI world.

Reference: MPI_Instance RAII init (dep/gemini/mpi.hpp:48) and the
partitions/rank topology carried by Graph (core/graph.hpp:98-105). Here the
"world" is a 1-D jax.sharding.Mesh over the partition axis ``p``; ICI
collectives replace the MPI ring. Multi-host scale-out keeps the same axis:
``maybe_initialize_distributed`` (MPI_Init's role) joins the processes, the
mesh spans all global devices ordered host-major so that ring neighbors are
intra-host except at host boundaries — the ppermute ring rides ICI within a
host and crosses DCN exactly (hosts - 1) times per rotation, the same
boundary structure as the reference's rank ring over machines
(comm/network.cpp:612-633, ranks laid out one per machine in hostfile).
"""

from __future__ import annotations

import os
from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh

from neutronstarlite_tpu.utils.logging import get_logger

PARTITION_AXIS = "p"
# the 2D (vertex x feature) mesh axes (parallel/partitioner.py): the
# vertex ring rotates over VERTEX_AXIS, feature slabs shard over
# FEATURE_AXIS (its all-reduce fires where the blocked kernels contract)
VERTEX_AXIS = "v"
FEATURE_AXIS = "f"
log = get_logger("mesh")
_dist_initialized = False


shard_map = jax.shard_map


def shard_leading(mesh: Mesh, a) -> jax.Array:
    """Device-put one host array sharded over its leading (partition)
    axis: the helper behind the table containers' ``shard()``."""
    from jax.sharding import NamedSharding, PartitionSpec as PS

    spec = PS(PARTITION_AXIS, *([None] * (np.ndim(a) - 1)))
    return jax.device_put(np.asarray(a), NamedSharding(mesh, spec))


def pcast_varying(x, axes):
    """``lax.pcast(x, axes, to="varying")``: mark a shard_map value as
    varying over ``axes`` for the VMA type system."""
    return jax.lax.pcast(x, axes, to="varying")


def maybe_initialize_distributed() -> None:
    """Join a multi-process JAX world when the environment asks for one —
    the MPI_Instance RAII equivalent (dep/gemini/mpi.hpp:48-56).

    Triggers: ``NTS_COORDINATOR`` (host:port) + ``NTS_NUM_PROCESSES`` +
    ``NTS_PROCESS_ID`` set explicitly (the mpiexec-style launch), or
    ``NTS_MULTIHOST=1`` for TPU-pod auto-detection (jax.distributed reads
    the pod metadata itself). Single-process runs are untouched.
    """
    global _dist_initialized
    if _dist_initialized:
        return
    coord = os.environ.get("NTS_COORDINATOR", "")
    auto = os.environ.get("NTS_MULTIHOST", "0") == "1"
    if not coord and not auto:
        return
    kwargs = {}
    if coord:
        kwargs = dict(
            coordinator_address=coord,
            num_processes=int(os.environ["NTS_NUM_PROCESSES"]),
            process_id=int(os.environ["NTS_PROCESS_ID"]),
        )
    jax.distributed.initialize(**kwargs)
    _dist_initialized = True
    log.info(
        "distributed world: process %d/%d, %d global devices",
        jax.process_index(),
        jax.process_count(),
        len(jax.devices()),
    )


def _host_major(devices):
    """Order devices host-major (process, then local id): ring neighbors stay
    on ICI inside each host; DCN is crossed only at host boundaries."""
    return sorted(devices, key=lambda d: (d.process_index, d.id))


def make_mesh(partitions: Optional[int] = None) -> Mesh:
    """1-D mesh over ``partitions`` global devices (default: all), host-major
    ordered (see module docstring).

    Multi-process: a partial mesh must contain addressable devices of EVERY
    process (each process shards onto the same global mesh), so the selection
    takes partitions/process_count devices from each host; a prefix of the
    host-major order would hand later hosts a mesh they own nothing of.
    """
    devices = _host_major(jax.devices())
    n = partitions or len(devices)
    if n > len(devices):
        raise ValueError(f"requested {n} partitions but only {len(devices)} devices")
    procs = jax.process_count()
    if procs > 1 and n < len(devices):
        if n % procs != 0:
            raise ValueError(
                f"PARTITIONS={n} must be a multiple of process count {procs}"
            )
        per = n // procs
        by_proc = {}
        for d in devices:
            by_proc.setdefault(d.process_index, []).append(d)
        chosen = []
        for pid in sorted(by_proc):
            if len(by_proc[pid]) < per:
                raise ValueError(
                    f"process {pid} has {len(by_proc[pid])} devices < {per}"
                )
            chosen.extend(by_proc[pid][:per])
        return Mesh(np.asarray(chosen), (PARTITION_AXIS,))
    return Mesh(np.asarray(devices[:n]), (PARTITION_AXIS,))


def validate_mesh_request(pv: int, pf: int) -> None:
    """Loud mesh-shape validation at the lifecycle funnel: a requested
    ``Pv x Pf`` that exceeds the visible device count dies HERE with a
    one-line error naming both numbers, instead of a deep shard_map trace
    later. Sim meshes honor ``jax_num_cpu_devices`` /
    ``--xla_force_host_platform_device_count``: the
    count checked is whatever ``jax.devices()`` reports on this rig."""
    if pv < 1 or pf < 1:
        raise ValueError(
            f"MESH:{pv},{pf} is not a mesh: both axes must be >= 1"
        )
    n = pv * pf
    have = len(jax.devices())
    if n > have:
        raise ValueError(
            f"MESH:{pv},{pf} needs {n} devices but only {have} are "
            f"visible on this rig (grow a sim mesh with "
            f"jax_num_cpu_devices / --xla_force_host_platform_device_count"
            f", or shrink the mesh)"
        )


def make_mesh2d(pv: int, pf: int) -> Mesh:
    """2D ``(vertex, feature)`` mesh over ``pv * pf`` devices, ICI/DCN-
    aware for multi-host: the FEATURE axis stays intra-host (its
    all-reduce blocks every layer's contraction, so it must ride ICI)
    while the VERTEX axis spans hosts — the ring hop it carries is
    overlapped with compute (dist_ring_blocked) and tolerates DCN
    latency, the T5X ``create_hybrid_device_mesh`` assignment
    (SNIPPETS.md [1]-[2]) with (vertex, feature) in the (data, model)
    roles. Single-host: a host-major reshape of the device list (the
    degenerate hybrid mesh)."""
    validate_mesh_request(pv, pf)
    devices = _host_major(jax.devices())
    n = pv * pf
    procs = jax.process_count()
    if procs > 1:
        if n != len(devices) or pv % procs != 0:
            raise ValueError(
                f"multi-host MESH:{pv},{pf} must span all {len(devices)} "
                f"global devices with the vertex axis a multiple of the "
                f"process count {procs} (each host contributes whole "
                "vertex-partition rows; the feature axis never crosses "
                "DCN)"
            )
        try:
            from jax.experimental import mesh_utils

            dm = mesh_utils.create_hybrid_device_mesh(
                (pv // procs, pf), (procs, 1), devices=devices
            )
            return Mesh(dm, (VERTEX_AXIS, FEATURE_AXIS))
        except Exception as e:  # pragma: no cover - topology-dependent
            log.warning(
                "create_hybrid_device_mesh failed (%s); falling back to "
                "the host-major reshape (feature axis may cross DCN)", e,
            )
    return Mesh(
        np.asarray(devices[:n]).reshape(pv, pf),
        (VERTEX_AXIS, FEATURE_AXIS),
    )
