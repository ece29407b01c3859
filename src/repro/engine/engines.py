"""The built-in engines behind :func:`repro.engine.cluster`.

========== =============================================================
name       backing pipeline
========== =============================================================
brute      O(n^2) host oracle (``brute_dbscan``) -- the ground truth the
           conformance suite holds every other engine to.
grit       paper-faithful host GriT-DBSCAN (Alg 6: grid tree +
           FastMerging + BFS over seed grids).
grit-ldf   host GriT-DBSCAN-LDF (union-find, low-density-first, §5.2).
device     fully in-graph jitted pipeline with *adaptive* static caps:
           estimated from grid statistics, grown geometrically on
           overflow (never silently truncated).  Naive-broadcast
           distance plane (the in-graph oracle).
device-kernels
           the same pipeline with ``use_kernels=True``: core/border
           distances go through the batched Pallas kernels (MXU-tiled
           on TPU; elsewhere a tiled loop that skips the candidate
           padding tail and early-exits core counts at MinPts -- see
           ``repro.kernels.ops``).
distributed spatial slab sharding + halo exchange + global label
           reconciliation over a jax mesh (shard_map), with the same
           adaptive cap loop wrapped around the whole SPMD program.
           The shard-local pipeline honors ``use_kernels`` (threaded
           through ``ClusterCaps.grit``; defaults to the Pallas kernel
           plane on TPU meshes) and reports per-point core flags plus
           slab/grid provenance -- the inputs of the sharded serving
           index (``repro.index.ShardedGritIndex``).
========== =============================================================

All engines take host numpy points and return
:class:`~repro.engine.result.ClusterResult` with labels in original
point order.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Optional

import numpy as np

from repro import obs
from repro.core.dbscan import brute_dbscan, grit_dbscan
from repro.core.validate import core_flags

from .adaptive import (adaptive_device_dbscan, adaptive_loop,
                       estimate_caps, estimate_shard_caps, grow_caps,
                       _pow2_at_least)
from .registry import register_engine
from .result import ClusterResult


@register_engine("brute", "O(n^2) host oracle (reference labels)")
def _brute_engine(points, eps, min_pts, *, chunk: int = 2048,
                  with_core: bool = True) -> ClusterResult:
    t0 = time.perf_counter()
    labels = brute_dbscan(points, eps, min_pts, chunk=chunk)
    core = core_flags(points, eps, min_pts, chunk=chunk) if with_core \
        else None
    return ClusterResult.build(
        labels, "brute", core=core,
        stats={"n": len(points), "t_total": time.perf_counter() - t0})


def _host_grit(points, eps, min_pts, variant: str, name: str,
               **opts) -> ClusterResult:
    r = grit_dbscan(points, eps, min_pts, variant=variant, **opts)
    return ClusterResult.build(r.labels, name, core=r.core, grid=r.grid,
                               stats=r.stats)


@register_engine("grit", "host GriT-DBSCAN (paper Algorithm 6)")
def _grit_engine(points, eps, min_pts, *, neighbor_engine: str = "tree",
                 merge_engine: str = "fast", rng=None) -> ClusterResult:
    return _host_grit(points, eps, min_pts, "grit", "grit",
                      neighbor_engine=neighbor_engine,
                      merge_engine=merge_engine, rng=rng)


@register_engine("grit-ldf",
                 "host GriT-DBSCAN-LDF (union-find, low-density first)")
def _grit_ldf_engine(points, eps, min_pts, *, neighbor_engine: str = "tree",
                     merge_engine: str = "fast", rng=None) -> ClusterResult:
    return _host_grit(points, eps, min_pts, "ldf", "grit-ldf",
                      neighbor_engine=neighbor_engine,
                      merge_engine=merge_engine, rng=rng)


def _pad_bucket(n: int, quantum: int = 128) -> int:
    """Pad n up to a coarse bucket so similarly-sized datasets hit the
    same jitted program instead of recompiling per exact n."""
    return max(quantum, (n + quantum - 1) // quantum * quantum)


# build_grids_device computes interval indices as floor((x - min)/side)
# in f32 and clamps them into [0, PAD_ID] before the int32 cast.  Both
# steps lose correctness silently once span/side gets large: beyond
# ~2^22 the f32 quotient's ulp approaches a whole grid cell, so a
# point's identifier can land cells away from its true cell and miss
# its eps-neighbors' stencils, and near 2^30 a top-edge valid point can
# round up onto the PAD_ID sentinel itself.  The in-graph pipeline
# cannot raise under jit, so the device-backed engines reject such
# inputs host-side here.  Host engines are unaffected (float64/int64
# identifiers).
def _check_device_grid_range(pts: np.ndarray, eps: float,
                             limit: int = 2 ** 22) -> None:
    d = pts.shape[1]
    side = float(eps) / np.sqrt(d)
    span = float((pts.max(axis=0) - pts.min(axis=0)).max())
    if span / side >= limit:
        raise ValueError(
            f"eps={eps} is too small for the coordinate span {span:.3g}: "
            f"span/side = {span / side:.3g} >= 2^22 exceeds the f32 "
            f"device-grid identifier range (grid assignment would "
            f"quantize by whole cells); rescale the data, increase eps, "
            f"or use a host engine (grit/grit-ldf)")


def _device_impl(points, eps, min_pts, name: str, *, caps=None,
                 use_kernels=None, max_retries: int = 8,
                 growth: float = 2.0,
                 pad_quantum: int = 128) -> ClusterResult:
    """Single-program XLA pipeline with the adaptive-cap driver.

    Points are padded to a coarse size bucket (``pad_quantum``) with
    masked-out sentinel points, so the jit cache is shared across
    datasets of similar size.
    """
    import jax.numpy as jnp

    t0 = time.perf_counter()
    with obs.stage("engine.device.prepare"):
        pts = np.asarray(points, np.float32)
        n, d = pts.shape
        _check_device_grid_range(pts, eps)
        n_pad = _pad_bucket(n, pad_quantum)
        padded = np.zeros((n_pad, d), np.float32)
        padded[:n] = pts
        dev_pts = jnp.asarray(padded)
        dev_valid = jnp.asarray(np.arange(n_pad) < n)

    res, attempts = adaptive_device_dbscan(
        dev_pts, eps, min_pts, caps, point_valid=dev_valid,
        max_retries=max_retries, growth=growth, use_kernels=use_kernels)
    with obs.stage("engine.device.fetch"):
        labels = np.asarray(res.labels)[:n].astype(np.int64)
        core = np.asarray(res.core)[:n]
        return ClusterResult.build(
            labels, name, core=core, attempts=attempts,
            overflow=attempts[-1]["overflow"],
            stats={"n": n, "n_padded": n_pad,
                   "retries": len(attempts) - 1,
                   "t_total": time.perf_counter() - t0})


@register_engine("device",
                 "in-graph jitted pipeline, adaptive static caps, "
                 "naive-broadcast distance plane")
def _device_engine(points, eps, min_pts, **opts) -> ClusterResult:
    opts.setdefault("use_kernels", False)
    return _device_impl(points, eps, min_pts, "device", **opts)


@register_engine("device-kernels",
                 "device pipeline with the batched Pallas distance "
                 "kernels (MXU on TPU, tiled early-exit loop elsewhere)")
def _device_kernels_engine(points, eps, min_pts, **opts) -> ClusterResult:
    opts.setdefault("use_kernels", True)
    return _device_impl(points, eps, min_pts, "device-kernels", **opts)


@register_engine("distributed",
                 "slab-sharded shard_map pipeline (halo exchange + "
                 "global label reconciliation), adaptive caps")
def _distributed_engine(points, eps, min_pts, *, mesh=None, caps=None,
                        use_kernels: Optional[bool] = None,
                        max_retries: int = 8,
                        growth: float = 2.0) -> ClusterResult:
    """Multi-device SPMD engine.

    ``mesh`` defaults to a 1-D mesh over every visible jax device.  Caps
    are estimated from *per-shard* grid statistics
    (:func:`repro.engine.estimate_shard_caps`): slab cuts land on grid
    lines, so the worst shard's own + ghost-band point set bounds every
    shard-local table without inflating each shard to the global one;
    the halo cap comes from the boundary-band census
    (``repro.dist.halo.boundary_bands``) instead of the densest-window
    upper bound that historically left halo buffers ~76% padding, and
    the same census's total feeds the always-on counter
    ``dist.halo.rows`` (rows the halo exchange selects, per fit whose
    caps the census sized).

    ``use_kernels`` selects the shard-local distance plane (it rides on
    ``ClusterCaps.grit`` -- the same static jit key as the caps): None
    defaults to the Pallas kernel plane on TPU meshes (where the MXU
    kernels are the point -- the choice ``engine="auto"`` inherits) and
    the naive broadcast plane elsewhere; an explicit flag always wins,
    including over the plane carried by a caller-provided ``caps``.
    """
    import jax
    from repro.dist import (ClusterCaps, boundary_bands, distributed_fit,
                            halo_cap_of)

    t0 = time.perf_counter()
    pts = np.asarray(points, np.float64)
    n, d = pts.shape
    _check_device_grid_range(pts, eps)
    if mesh is None:
        mesh = jax.make_mesh((jax.device_count(),), ("shard",))
    if caps is None:
        uk = (jax.default_backend() == "tpu") if use_kernels is None \
            else bool(use_kernels)
        n_shards = int(mesh.devices.size)
        with obs.stage("engine.census"):
            grit = estimate_shard_caps(pts, eps, min_pts, n_shards,
                                       use_kernels=uk)
            bands = boundary_bands(pts, eps, n_shards)
            halo = min(halo_cap_of(bands), _pow2_at_least(n))
        # the rows this fit's halo exchange selects, over all sides
        obs.counter("dist.halo.rows").inc(int(bands.sum()))
        caps = ClusterCaps(grit=grit, halo_cap=halo)
    elif use_kernels is not None and \
            caps.grit.use_kernels != bool(use_kernels):
        caps = dataclasses.replace(
            caps, grit=dataclasses.replace(caps.grit,
                                           use_kernels=bool(use_kernels)))

    def run(c):
        fit = distributed_fit(pts, eps, min_pts, mesh, caps=c)
        return fit, fit.report

    def grow(c, overflowed):
        # halo is measured from the raw points, so its flag stays
        # trustworthy even while the grid table is truncated
        grit = c.grit
        grit_flags = tuple(f for f in overflowed if f != "halo")
        if grit_flags:
            grit = grow_caps(grit, grit_flags, n=n, d=d, growth=growth)
        halo = c.halo_cap
        if "halo" in overflowed:
            halo = _pow2_at_least(min(int(halo * growth), n))
        return ClusterCaps(grit=grit, halo_cap=halo)

    fit, attempts = adaptive_loop(
        run, grow,
        lambda c: {**dataclasses.asdict(c.grit), "halo_cap": c.halo_cap},
        caps, max_retries)
    return ClusterResult.build(
        fit.labels, "distributed", core=fit.core, attempts=attempts,
        overflow=attempts[-1]["overflow"],
        stats={"n": n, "n_shards": mesh.devices.size,
               "retries": len(attempts) - 1,
               "use_kernels": caps.grit.use_kernels,
               "t_total": time.perf_counter() - t0})
