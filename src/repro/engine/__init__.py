"""engine: the unified clustering API (registry + adaptive-cap driver).

    from repro.engine import cluster
    result = cluster(points, eps=3000.0, min_pts=10)   # engine="auto"

See DESIGN.md §3 for the architecture.
"""

from .result import ClusterResult
from .registry import (available_engines, cluster, engine_descriptions,
                       get_engine, register_engine, resolve_auto)
from .adaptive import (CapOverflowError, adaptive_device_dbscan,
                       adaptive_loop, estimate_caps, estimate_shard_caps,
                       grow_caps, grid_stats, stencil_census,
                       stencil_neighbor_bound)

__all__ = [
    "ClusterResult", "cluster", "available_engines", "engine_descriptions",
    "get_engine", "register_engine", "resolve_auto",
    "CapOverflowError", "adaptive_device_dbscan", "adaptive_loop",
    "estimate_caps", "estimate_shard_caps", "grow_caps",
    "grid_stats", "stencil_census", "stencil_neighbor_bound",
]
