from neutronstarlite_tpu.models.base import ToolkitBase, register_algorithm, get_algorithm
import neutronstarlite_tpu.models.gcn  # noqa: F401  (registers GCN variants)
import neutronstarlite_tpu.models.gcn_dist  # noqa: F401  (registers GCNDIST)
import neutronstarlite_tpu.models.gcn_dist_cache  # noqa: F401  (registers GCNDISTMIRROR/CACHE)
import neutronstarlite_tpu.models.gat  # noqa: F401  (registers GAT variants)
import neutronstarlite_tpu.models.gat_dist  # noqa: F401  (registers GATDIST)
import neutronstarlite_tpu.models.gin  # noqa: F401  (registers GIN variants)
import neutronstarlite_tpu.models.gin_dist  # noqa: F401  (registers GINDIST)
import neutronstarlite_tpu.models.ggcn  # noqa: F401  (registers GGCN)
import neutronstarlite_tpu.models.ggcn_dist  # noqa: F401  (registers GGCNDIST)
import neutronstarlite_tpu.models.commnet  # noqa: F401  (registers CommNet)
import neutronstarlite_tpu.models.commnet_dist  # noqa: F401  (registers COMMNETDIST)
import neutronstarlite_tpu.models.gcn_sample  # noqa: F401  (registers GCNSAMPLE)
import neutronstarlite_tpu.models.test_getdep  # noqa: F401  (registers TEST_GETDEP*)
import neutronstarlite_tpu.models.seqlm  # noqa: F401  (registers SEQLM)

__all__ = ["ToolkitBase", "register_algorithm", "get_algorithm"]
