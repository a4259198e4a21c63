"""tools/aot_check spec builders: lower+compile on the CPU mesh (the
topology-targeted path swaps only the mesh's devices)."""

from __future__ import annotations

import os

import numpy as np
import pytest

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as PS

from neutronstarlite_tpu.tools.aot_check import (
    _dist_gcn_case,
    _single_device_case,
)
from neutronstarlite_tpu.utils.config import InputInfo

ROOT = os.path.dirname(os.path.dirname(__file__))
CFG_DIR = os.path.join(ROOT, "configs")
FIXTURE = os.path.join(ROOT, "tests", "fixtures", "cora")


def _cora_cfg(algorithm):
    """configs/gcn_cora.cfg over the committed Cora fixture (its own paths
    point at the reference checkout, which no machine of this round has;
    the featuretable is not shipped: random fallback)."""
    cfg = InputInfo.read_from_cfg_file(os.path.join(CFG_DIR, "gcn_cora.cfg"))
    cfg.algorithm = algorithm
    cfg.edge_file = os.path.join(FIXTURE, "cora.2708.edge.self")
    cfg.feature_file = ""
    cfg.label_file = os.path.join(FIXTURE, "cora.labeltable")
    cfg.mask_file = os.path.join(FIXTURE, "cora.mask")
    return cfg


@pytest.mark.parametrize("algorithm", ["GCNCPU", "GATCPU", "GINCPU", "GGCNCPU"])
def test_single_device_case_compiles(algorithm):
    mesh1 = Mesh(np.array(jax.devices()[:1]), ("one",))
    rep = NamedSharding(mesh1, PS())
    cfg = _cora_cfg(algorithm)
    jitted, shapes = _single_device_case(cfg, CFG_DIR, rep)
    compiled = jitted.lower(*shapes).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes > 0


def _tiny_dist_case(rng, comm_layer, kernel_tile=0):
    """(cfg, mesh, edges) of a GCNDIST run over conftest's tiny graph."""
    from neutronstarlite_tpu.parallel.mesh import PARTITION_AXIS
    from tests.conftest import tiny_graph

    devs = jax.devices()
    if len(devs) < 4:
        pytest.skip("needs the 8-virtual-device rig")
    mesh = Mesh(np.array(devs[:4]), (PARTITION_AXIS,))
    g, _ = tiny_graph(rng, v_num=97, e_num=800)
    cfg = InputInfo()
    cfg.algorithm = "GCNDIST"
    cfg.vertices = g.v_num
    cfg.layer_string = "12-8-3"
    cfg.comm_layer = comm_layer
    cfg.partitions = 4
    cfg.kernel_tile = kernel_tile
    return cfg, mesh, (g.row_indices, g.dst_of_edge)


@pytest.mark.parametrize(
    "comm_layer,kernel_tile",
    [("ring", 0), ("ell", 0), ("mirror", 0), ("ell", 512)],
)
def test_dist_gcn_case_compiles(rng, comm_layer, kernel_tile):
    # 512 -> the dist blocked (KERNEL_TILE) spec path
    cfg, mesh, edges = _tiny_dist_case(rng, comm_layer, kernel_tile)
    jitted, shapes, kind = _dist_gcn_case(cfg, None, mesh, edges=edges)
    assert kind == comm_layer
    compiled = jitted.lower(*shapes).compile()
    assert compiled.memory_analysis().argument_size_in_bytes > 0


@pytest.mark.parametrize("comm_layer", ["ring", "ell", "mirror"])
def test_dist_spec_parity_with_trainer(rng, comm_layer):
    """The tool and the trainer take their layout from the one builder
    (parallel/layouts.build_exchange): the same ``blocks`` type, and the
    same pytree structure, shapes, dtypes and PartitionSpecs in every
    train-step argument."""
    from neutronstarlite_tpu.graph.dataset import GNNDatum
    from neutronstarlite_tpu.models.gcn_dist import DistGCNTrainer

    cfg, mesh, (src, dst) = _tiny_dist_case(rng, comm_layer)
    _, shapes, _ = _dist_gcn_case(cfg, None, mesh, edges=(src, dst))
    sizes = cfg.layer_sizes()
    datum = GNNDatum.random_generate(cfg.vertices, sizes[0], sizes[-1])
    tr = DistGCNTrainer.from_arrays(cfg, src, dst, datum)
    real = tr.aot_args()
    assert type(shapes[2]) is type(tr.blocks)

    def sig(x):
        if hasattr(x, "shape"):
            spec = getattr(getattr(x, "sharding", None), "spec", None)
            # a fresh single-device array (the PRNG key) is replicated in
            # spirit; normalize its spec-less sharding to PartitionSpec()
            s = "PartitionSpec()" if spec is None else str(spec)
            return (tuple(x.shape), str(x.dtype), s)
        return x

    a = jax.tree.map(sig, shapes)
    b = jax.tree.map(sig, real)
    assert jax.tree.structure(a) == jax.tree.structure(b)
    assert jax.tree.leaves(a) == jax.tree.leaves(b)


def test_bind_forward_precision_gate():
    """The bf16 binding (ONE definition: DistGATTrainer.bind_forward,
    shared with tools/aot_check) engages exactly on PRECISION:bfloat16
    and passes compute_dtype through to the layer fn."""
    import functools

    import jax.numpy as jnp

    from neutronstarlite_tpu.models.gat_dist import (
        DistGATTrainer,
        dist_gat_forward,
    )
    from neutronstarlite_tpu.models.ggcn_dist import DistGGCNTrainer
    from neutronstarlite_tpu.utils.config import InputInfo

    cfg = InputInfo()
    assert DistGATTrainer.bind_forward(cfg) is dist_gat_forward  # f32: unbound
    cfg.precision = "bfloat16"
    bound = DistGATTrainer.bind_forward(cfg)
    assert isinstance(bound, functools.partial)
    assert bound.keywords == {"compute_dtype": jnp.bfloat16}
    # GGCN inherits the binding with ITS forward
    gbound = DistGGCNTrainer.bind_forward(cfg)
    assert gbound.func is DistGGCNTrainer.model_forward_fn
    assert gbound.keywords == {"compute_dtype": jnp.bfloat16}
