"""nts-tpu: a TPU-native distributed GNN training framework.

A ground-up JAX/XLA/Pallas re-design of the capabilities of NeutronStar
(iDC-NEU/NeutronStarLite, SIGMOD'22): full-batch and mini-batch training of
GCN / GAT / GIN / CommNet on partitioned graphs, with master/mirror dependency
management, fused sparse aggregation operators with hand-paired backward
passes, edge-level operators, fan-out neighbor sampling, and data-parallel
model sync.

Where the reference is C++/MPI/OpenMP/libtorch/CUDA, this framework is
TPU-first:

- graph storage      : HBM-resident CSC/CSR device arrays, vertex-sharded
                       (reference: core/GraphSegment.h, core/PartitionedGraph.hpp)
- aggregation ops    : segment-sum / Pallas kernels with custom_vjp pairs
                       (reference: core/nts*GraphOp.hpp, cuda/ntsCUDAFuseKernel.cuh)
- distribution       : jax.sharding.Mesh + shard_map, ppermute ring exchange
                       over ICI in place of the MPI master/mirror ring
                       (reference: comm/network.cpp, core/graph.hpp engines)
- autodiff           : jax.grad end-to-end; custom_vjp where the reference
                       hand-pairs forward/backward (reference: core/ntsContext.hpp)
- models             : toolkit-style trainers driven by the same KEY:VALUE cfg
                       files (reference: toolkits/, GraphSegment.cpp:222)
"""

import time as _time

_T_IMPORT = _time.perf_counter()  # the first line the package runs

__version__ = "0.1.0"

from neutronstarlite_tpu.utils.config import InputInfo  # noqa: E402,F401
from neutronstarlite_tpu.utils.platform import note_package_import as _note  # noqa: E402

_note(_T_IMPORT)  # the ``process_prelude`` span, for the process's first tracer
