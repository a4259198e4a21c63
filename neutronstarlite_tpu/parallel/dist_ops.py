"""Distributed aggregation: the ppermute ring over ICI.

This is the TPU-native replacement for the reference's ring-ordered MPI
master/mirror exchange overlapped with aggregation:

- forward  <- process_edges_forward_decoupled / sync_compute_decoupled
  (graph.hpp:2644/:3640): at ring step s, device p holds the feature shard of
  partition q = (p + s) % P and applies the (p, q) edge block's weighted
  scatter-add into its local accumulator, then the shard moves one hop along
  the ring (ppermute), exactly the reference's ``(pid +- step) % partitions``
  schedule (network.cpp:612-633).
- backward <- process_edges_backward_decoupled / compute_sync_decoupled
  (graph.hpp:3123/:3456): produced automatically by jax.grad — the transpose
  of ppermute is the reverse-direction ppermute and the transpose of the
  block scatter-add is the block gather, so the generated backward is the
  reverse ring with gradient push that the reference hand-writes.
- XLA's async collectives give the compute/communication overlap the
  reference implements with dedicated Send/Recv threads + spin queues
  (rtminfo->process_overlap, network.cpp:769-782): the next shard's ppermute
  can be in flight while the current block's scatter-add runs.

Shapes are static: shards are [vp, f] padded, blocks are [P, Eb] per device.
Padding edges have weight 0 and index vertex 0 of their shard.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as PS

from neutronstarlite_tpu.ops.aggregate import _scatter_accumulate
from neutronstarlite_tpu.parallel.mesh import PARTITION_AXIS, shard_map


def _ring_aggregate_local_steps(step_blocks, x_local, *,
                                partitions: int, vp: int, edge_chunk: int):
    """Step-major per-device body: step_blocks[s] = ([Eb_s] src, dst, w) —
    already this device's block for ring step s (row p of the stacked
    [P, Eb_s] arrays), so there is no dynamic block indexing and each step
    pays only its own diagonal's padding (DistGraph.step_blocks)."""
    # accumulate WIDE regardless of the exchange dtype (bf16 ships half
    # the ppermute bytes; the per-vertex sum must not round per term —
    # r5 review caught the bf16 accumulator here)
    acc = jnp.zeros((vp, x_local.shape[1]), dtype=jnp.float32)
    cur = x_local
    fwd_perm = [(i, (i - 1) % partitions) for i in range(partitions)]
    for s, (src, dst, w) in enumerate(step_blocks):
        acc = _scatter_accumulate(
            src, dst, w, cur, vp, edge_chunk, acc.dtype, acc=acc
        )
        if s != partitions - 1:
            cur = lax.ppermute(cur, PARTITION_AXIS, fwd_perm)
    return acc.astype(x_local.dtype)


def dist_gather_dst_from_src(mesh: Mesh, blocks, x: jax.Array) -> jax.Array:
    """out[v] = sum over in-edges of w * x[src], vertex-sharded over the mesh.

    ``blocks`` is the RingBlocks of ``DistGraph.shard`` (step-major per-step
    [P, Eb_s] triples); ``x`` is the padded [P*vp, f] feature array (sharded
    or shardable over axis 0); returns the aggregated array with the same
    layout. Differentiable (the backward is the reverse ring)."""
    n_steps = len(blocks.src)

    def local_steps(*args):
        xs = args[-1]
        # shard_map passes [1, Eb_s] rows; squeeze the device axis
        steps = [
            (args[s][0], args[n_steps + s][0], args[2 * n_steps + s][0])
            for s in range(n_steps)
        ]
        return _ring_aggregate_local_steps(
            steps, xs, partitions=n_steps, vp=blocks.vp,
            edge_chunk=blocks.edge_chunk,
        )

    fn = shard_map(
        local_steps,
        mesh=mesh,
        in_specs=tuple(PS(PARTITION_AXIS, None) for _ in range(3 * n_steps))
        + (PS(PARTITION_AXIS, None),),
        out_specs=PS(PARTITION_AXIS, None),
    )
    return fn(*blocks.src, *blocks.dst, *blocks.wgt, x)


def ring_aggregate_simulated(dist, x_padded: jax.Array) -> jax.Array:
    """Single-device simulation of the exact ring schedule — same blocks, same
    per-step accumulation order as _ring_aggregate_local_steps, with ppermute
    replaced by explicit shard rotation. Used by the test rig (one-core CI
    cannot execute real cross-device collectives) to pin down the block
    construction and schedule; the shard_map path itself is exercised by the
    multi-chip dryrun (__graft_entry__.dryrun_multichip)."""
    P, vp, f = dist.partitions, dist.vp, x_padded.shape[1]
    shards = [x_padded[p * vp : (p + 1) * vp] for p in range(P)]
    bs, bd, bw = (
        jnp.asarray(dist.block_src),
        jnp.asarray(dist.block_dst),
        jnp.asarray(dist.block_weight),
    )
    outs = []
    for p in range(P):
        acc = jnp.zeros((vp, f), dtype=x_padded.dtype)
        for s in range(P):
            q = (p + s) % P
            acc = _scatter_accumulate(
                bs[p, q], bd[p, q], bw[p, q], shards[q], vp, dist.edge_chunk,
                acc.dtype, acc=acc,
            )
        outs.append(acc)
    return jnp.concatenate(outs, axis=0)


def replicated(mesh: Mesh, tree):
    """Device-put a pytree fully replicated over the mesh (init_parameter
    broadcast's role, NtsScheduler.hpp:716)."""
    sh = NamedSharding(mesh, PS())
    return jax.tree.map(lambda a: jax.device_put(a, sh), tree)


def vertex_sharded(mesh: Mesh, arr):
    """Device-put a [P*vp, ...] padded vertex array sharded over axis 0."""
    ndim = jnp.ndim(arr)
    sh = NamedSharding(mesh, PS(PARTITION_AXIS, *([None] * (ndim - 1))))
    return jax.device_put(arr, sh)
