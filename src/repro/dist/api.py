"""Host-facing entry points of the distributed plane.

:func:`distributed_fit` is the full fit: pre-shard on the host, run the
cached SPMD step, unpermute -- returning, in original point order, the
globally reconciled labels *plus* the fitted provenance (core flags,
per-shard device grid rows) and the slab geometry (owning shard and cut
coordinates) that :class:`repro.index.ShardedGritIndex` builds from.

:func:`distributed_dbscan` keeps the legacy (labels, report) contract
on top of it.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import obs
from repro.core.device_dbscan import OverflowReport

from .halo import census_halo_cap
from .sharding import pack_slabs, slab_cuts, unshard_by_perm
from .step import ClusterCaps, cached_cluster_step


@dataclasses.dataclass
class DistributedFitResult:
    """One distributed fit, unpermuted to original point order.

    ``point_grid`` is *per-shard* provenance: the device grid-table row
    of each point within its owning shard's local pipeline (f32
    identifiers -- provenance and diagnostics, not the float64 host
    partition, which the serving index rebuilds per slab).
    """

    labels: np.ndarray       # [n] int64 global cluster ids; -1 noise
    core: np.ndarray         # [n] bool core-point flags
    point_grid: np.ndarray   # [n] int32 per-shard device grid rows
    shard_of: np.ndarray     # [n] int64 owning shard of each point
    cut_coords: np.ndarray   # [n_shards - 1] float64 slab boundaries
    report: OverflowReport   # per-cap flags OR-ed over shards


def distributed_fit(points: np.ndarray, eps: float, min_pts: int,
                    mesh: Mesh, caps: Optional[ClusterCaps] = None,
                    pad_to: Optional[int] = None) -> DistributedFitResult:
    """Pre-shard, run the SPMD cluster step, unpermute (vectorized).

    The report is truthy iff any static cap overflowed on any shard; a
    truthy report means every array is a truncated artifact and must
    not be trusted (the adaptive driver in ``repro.engine`` grows the
    caps and retries before letting that escape).

    Traced or not, the fit runs the same fused SPMD step under the span
    ``dist.fit.spmd_step``; the device time of its halo exchange, local
    fit and reconcile is attributed by the step's ``dist.halo`` /
    ``dist.local`` / ``dist.reconcile`` named scopes in a profiler
    trace.  The host stages ``dist.fit.pack``, ``dist.fit.transfer`` and
    ``dist.fit.unpack`` are ``obs.stage``s: each call observes their
    seconds into the always-on histograms ``dist.fit.pack_s`` etc.,
    traced or not.
    """
    pts = np.asarray(points, np.float64)
    n = pts.shape[0]
    axes = tuple(mesh.axis_names)
    n_shards = int(np.prod([mesh.shape[a] for a in axes]))
    if caps is None:
        # default grit caps, but a halo cap sized from the actual
        # boundary-band census (the adaptive engine additionally sizes
        # the grit caps per shard; see repro.engine.estimate_shard_caps)
        caps = ClusterCaps(halo_cap=census_halo_cap(pts, eps, n_shards))
    with obs.span("dist.fit", n=n, shards=n_shards):
        with obs.stage("dist.fit.pack"):
            order, cut_idx, cut_coords = slab_cuts(pts, eps, n_shards)
            pts_sh, valid_sh, perm = pack_slabs(pts, order, cut_idx,
                                                pad_to=pad_to)
        cap = pts_sh.shape[1]
        with obs.stage("dist.fit.transfer") as sp:
            flat_pts = jnp.asarray(pts_sh.reshape(n_shards * cap, -1))
            flat_valid = jnp.asarray(valid_sh.reshape(-1))
            sharding = NamedSharding(mesh, P(axes))
            flat_pts = jax.device_put(
                flat_pts, NamedSharding(mesh, P(axes, None)))
            flat_valid = jax.device_put(flat_valid, sharding)
            sp.sync(flat_pts, flat_valid)

        step = cached_cluster_step(mesh, eps, min_pts, caps, cap,
                                   pts.shape[1])
        with obs.span("dist.fit.spmd_step") as sp:
            labels, core, point_grid, report = step(flat_pts, flat_valid)
            sp.sync(labels, core, point_grid)
        report = jax.device_get(report)

        with obs.stage("dist.fit.unpack"):
            labels = unshard_by_perm(np.asarray(labels), perm,
                                     n).astype(np.int64)
            core = unshard_by_perm(np.asarray(core), perm, n, fill=False)
            point_grid = unshard_by_perm(np.asarray(point_grid), perm, n)
            shard_row = np.repeat(
                np.arange(n_shards, dtype=np.int64)[:, None], cap, axis=1)
            shard_of = unshard_by_perm(shard_row, perm, n)
    return DistributedFitResult(labels=labels, core=core,
                                point_grid=point_grid, shard_of=shard_of,
                                cut_coords=cut_coords, report=report)


def distributed_dbscan(points: np.ndarray, eps: float, min_pts: int,
                       mesh: Mesh, caps: Optional[ClusterCaps] = None,
                       pad_to: Optional[int] = None
                       ) -> Tuple[np.ndarray, OverflowReport]:
    """Legacy wrapper: (labels in original point order, report).

    The report is a fresh host instance (``jax.device_get`` of the
    OR-reduced shard flags) -- callers may keep or mutate it freely.
    ``bool(report)`` keeps the legacy overflow-flag contract.
    """
    res = distributed_fit(points, eps, min_pts, mesh, caps=caps,
                          pad_to=pad_to)
    return res.labels, res.report
