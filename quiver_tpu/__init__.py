"""quiver_tpu — TPU-native graph-learning data layer.

A ground-up JAX/XLA/Pallas rebuild of the capabilities of
quiver-team/torch-quiver (reference at ``/root/reference``): k-hop neighbor
sampling, cached/sharded feature collection, cross-host feature exchange,
partitioning tools, and a GNN serving pipeline — designed for TPU (static
shapes, device meshes, XLA collectives) rather than translated from CUDA.

Public API parity map (reference ``srcs/python/quiver/__init__.py:1-21``):

  Feature, DistFeature, PartitionInfo      -> quiver_tpu.feature / .dist
  GraphSageSampler, MixedGraphSageSampler  -> quiver_tpu.sampler / .mixed
  SampleJob                                -> quiver_tpu.mixed
  CSRTopo                                  -> quiver_tpu.utils.topology
  p2pCliqueTopo / init_p2p                 -> quiver_tpu.utils.mesh (MeshTopo)
  NcclComm / getNcclId                     -> quiver_tpu.dist.comm (TpuComm)
  quiver_partition_feature, load_...       -> quiver_tpu.partition
  generate_neighbour_num                   -> quiver_tpu.neighbour_num
  RequestBatcher/HybridSampler/InferenceServer -> quiver_tpu.serving
"""

import os as _os

if _os.environ.get("QUIVER_SANITIZE") == "1":
    # Lock-witness sanitizer (quiverlint v2's dynamic half): must patch
    # threading.Lock/RLock BEFORE any other quiver module imports so
    # module- and instance-level locks constructed below get wrapped.
    # analysis.witness is stdlib-only — no jax cost on this path.
    from .analysis import witness as _witness

    _witness.install()

from . import config
from .utils.topology import CSRTopo, coo_to_csr, parse_size, reindex_feature
from .utils.mesh import MeshTopo, make_mesh
from .sampler import GraphSageSampler, SampledBatch, LayerBlock
from .loader import SeedLoader
from .pipeline import make_fused_train_step, make_fused_eval_fn
from .mixed import MixedGraphSageSampler, SampleJob
from .feature import Feature, DeviceConfig
from .dist.feature import DistFeature, PartitionInfo
from .dist.comm import TpuComm
from .dist.sampler import DistGraphSampler
from .dist.ring import RingFeature
from .dist.init import initialize as distributed_initialize, make_hybrid_mesh
from .dist.hier import HierFeature
from .uva import UVAGraph
from .utils.rng import make_key
from .interop import to_torch_adjs, TorchSampleLoader
from .partition import (
    partition_without_replication,
    quiver_partition_feature,
    load_quiver_feature_partition,
)
from .hetero import (
    HeteroCSRTopo,
    HeteroGraphSageSampler,
    HeteroSampledBatch,
    HeteroLayerBlock,
    HeteroFeature,
)
from .neighbour_num import generate_neighbour_num
from . import multiprocessing  # registers mp reducers (parity: P10)
from .serving import (
    RequestBatcher,
    HybridSampler,
    InferenceServer,
    InferenceServer_Debug,
)

if _os.environ.get("QUIVER_SANITIZE") == "1":
    # Device-transfer witness (quiverlint v3's dynamic half) installs at
    # the END of import — unlike the lock witness it wraps jax's array
    # type, which must exist first.  Arms the `staging.no_sync()` region
    # gate as a side effect.
    from .analysis import transfer_witness as _transfer_witness

    _transfer_witness.install()

__version__ = "0.1.0"

__all__ = [
    "CSRTopo", "coo_to_csr", "parse_size", "reindex_feature",
    "MeshTopo", "make_mesh",
    "GraphSageSampler", "SampledBatch", "LayerBlock", "SeedLoader", "make_fused_train_step", "make_fused_eval_fn",
    "MixedGraphSageSampler", "SampleJob",
    "HeteroCSRTopo", "HeteroGraphSageSampler", "HeteroSampledBatch",
    "HeteroLayerBlock", "HeteroFeature",
    "Feature", "DeviceConfig",
    "DistFeature", "PartitionInfo", "TpuComm", "DistGraphSampler",
    "RingFeature", "distributed_initialize", "make_hybrid_mesh",
    "HierFeature", "UVAGraph", "make_key",
    "to_torch_adjs", "TorchSampleLoader",
    "partition_without_replication", "quiver_partition_feature",
    "load_quiver_feature_partition",
    "generate_neighbour_num",
    "RequestBatcher", "HybridSampler", "InferenceServer",
    "InferenceServer_Debug",
]
