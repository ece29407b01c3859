"""dist: the distributed serving subsystem (slab sharding + halo
exchange + SPMD step + label reconciliation).

What used to be one file (``repro.core.distributed``, kept as a compat
shim) is now a package with one module per concern:

* :mod:`repro.dist.sharding`  -- host-side slab partition: grid-line
  cuts along dim 0, vectorized shard packing/unpacking, halo bound.
* :mod:`repro.dist.halo`      -- device-side halo compaction (the fixed
  cap buffers exchanged between neighbor shards).
* :mod:`repro.dist.rebalance` -- load-triggered topology policy: EWMA
  per-shard load, bounded split-hottest / merge-coldest actuation on a
  :class:`repro.index.ShardedGritIndex`.
* :mod:`repro.dist.reconcile` -- cross-shard label reconciliation: edge
  construction over shared core points + the replicated global
  component map.
* :mod:`repro.dist.step`      -- ``ClusterCaps`` and the ``shard_map``
  SPMD cluster step (jit cache with oldest-entry eviction); the
  shard-local pipeline is the full ``device_dbscan``, including the
  kernelized distance plane when ``caps.grit.use_kernels`` is set.
* :mod:`repro.dist.api`       -- the host-facing entry points:
  :func:`distributed_fit` (labels + core flags + grid provenance; feeds
  :class:`repro.index.ShardedGritIndex`) and the legacy
  :func:`distributed_dbscan` (labels, report).

See DESIGN.md §5 for the sharding strategy and exactness argument.
"""

from .sharding import (halo_bound, owner_of_slab, shard_points_by_slab,
                       slab_cuts)
from .halo import (boundary_bands, boundary_census, census_halo_cap,
                   halo_buffer, halo_cap_of)
from .rebalance import RebalancePolicy, Rebalancer
from .step import ClusterCaps, cached_cluster_step, make_cluster_step
from .api import DistributedFitResult, distributed_dbscan, distributed_fit

__all__ = [
    "ClusterCaps", "DistributedFitResult", "RebalancePolicy", "Rebalancer",
    "boundary_bands", "boundary_census", "cached_cluster_step",
    "census_halo_cap", "distributed_dbscan", "distributed_fit",
    "halo_bound", "halo_buffer", "halo_cap_of",
    "make_cluster_step", "owner_of_slab", "shard_points_by_slab",
    "slab_cuts",
]
