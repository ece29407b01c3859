"""The SPMD cluster step: one ``shard_map`` over the flattened mesh.

Per shard: halo exchange (``repro.dist.halo``), the exact local
GriT-DBSCAN pipeline on own + ghost points (``device_dbscan`` -- the
*full* device pipeline, so ``caps.grit.use_kernels`` routes the shard's
core/border distance plane through the batched Pallas kernels exactly
as on a single device), then cross-shard label reconciliation
(``repro.dist.reconcile``).

The step returns, per shard, the globally reconciled labels *and* the
fitted provenance the serving plane keeps: per-point core flags and the
device grid row of every own point (``point_grid``).  That is what lets
``distributed_fit`` feed a :class:`repro.index.ShardedGritIndex`
without re-deriving core status host-side.

Each shard sends its boundary buffers to the adjacent shard with
``jax.lax.ppermute`` (ring permutation; the slab ends are masked off --
shard 0 has no left neighbor) and the ghosts' locally assigned labels
travel back over the same permutation, reversed.

Compiled steps are cached by everything that shapes the program; the
cache evicts its *oldest* entry at capacity (insertion order, refreshed
on hit) so an adaptive-cap retry loop -- which alternates between at
most two keys -- can never evict the step it is about to reuse.
"""

from __future__ import annotations

import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.device_dbscan import (GritCaps, OverflowReport, PAD_COORD,
                                      device_dbscan)

from .halo import halo_buffer
from .reconcile import global_component_map, shared_point_edges


@dataclasses.dataclass(frozen=True)
class ClusterCaps:
    """Static caps of the distributed pipeline: the per-shard device
    caps (including the ``use_kernels`` distance-plane switch, which is
    part of the same static jit key) plus the halo/edge exchange caps."""

    grit: GritCaps = GritCaps()
    halo_cap: int = 512          # max points shipped per boundary side;
                                 # also sizes the reconciliation edge
                                 # buffers (one edge per shipped point)


def make_cluster_step(mesh: Mesh, eps, min_pts: int, caps: ClusterCaps,
                      n_points_shard: int, d: int):
    """Build the SPMD cluster step for ``mesh`` (all axes flattened).

    Returns a jit-able fn: (points [N, d] f32, valid [N] bool) ->
    (labels [N] int32 global cluster ids (-1 noise),
     core [N] bool core-point flags,
     point_grid [N] int32 per-shard device grid rows (provenance),
     overflow ``OverflowReport`` with per-cap flags OR-ed over shards),
    with N = n_shards * n_points_shard sharded over all mesh axes.
    """
    axes = tuple(mesh.axis_names)
    n_shards = int(np.prod([mesh.shape[a] for a in axes]))
    L = caps.grit.grid_cap          # per-shard label space
    H = caps.halo_cap

    def local_step(pts, valid):
        # shard_map hands us the local block: [n_points_shard, d]
        me = jax.lax.axis_index(axes)
        # --- 1. halo exchange (both directions, ring) ---
        with jax.named_scope("dist.halo"):
            lo_buf, lo_idx, ov1 = halo_buffer(pts, valid, eps, "lo", H)
            hi_buf, hi_idx, ov2 = halo_buffer(pts, valid, eps, "hi", H)
            right = [(i, (i + 1) % n_shards) for i in range(n_shards)]
            left = [((i + 1) % n_shards, i) for i in range(n_shards)]
            # my hi-edge points go to the right neighbor; lo-edge to the left
            ghosts_from_left = jax.lax.ppermute(hi_buf, axes, right)
            ghosts_from_right = jax.lax.ppermute(lo_buf, axes, left)
            # ring wrap: shard 0 has no left neighbor in a slab decomposition
            first = me == 0
            last = me == n_shards - 1
            ghosts_from_left = jnp.where(first, PAD_COORD, ghosts_from_left)
            ghosts_from_right = jnp.where(last, PAD_COORD, ghosts_from_right)

        # --- 2. local exact GriT-DBSCAN on own + ghosts ---
        with jax.named_scope("dist.local"):
            all_pts = jnp.concatenate([pts, ghosts_from_left,
                                       ghosts_from_right])
            all_valid = jnp.concatenate([
                valid,
                jnp.any(ghosts_from_left < PAD_COORD / 2, axis=1),
                jnp.any(ghosts_from_right < PAD_COORD / 2, axis=1)])
            res = device_dbscan(all_pts.astype(jnp.float32), eps, min_pts,
                                caps.grit, point_valid=all_valid)
        n_own = pts.shape[0]
        own_labels = res.labels[:n_own]
        own_core = res.core[:n_own]
        own_grid = res.point_grid[:n_own]
        ghost_l_labels = res.labels[n_own:n_own + H]
        ghost_l_core = res.core[n_own:n_own + H]
        ghost_r_labels = res.labels[n_own + H:]
        ghost_r_core = res.core[n_own + H:]

        # --- 3. reconcile: my labels of the ghosts go back to their home
        with jax.named_scope("dist.reconcile"):
            back_to_left = jnp.where(ghost_l_core, ghost_l_labels, -1)
            back_to_right = jnp.where(ghost_r_core, ghost_r_labels, -1)
            # label the ghosts got at the neighbor, aligned with my halo idx
            hi_remote = jax.lax.ppermute(back_to_left, axes, left)
            lo_remote = jax.lax.ppermute(back_to_right, axes, right)

            e_hi, ok_hi = shared_point_edges(
                own_labels, own_core, hi_idx, hi_remote, me,
                jnp.minimum(me + 1, n_shards - 1), L)
            e_lo, ok_lo = shared_point_edges(
                own_labels, own_core, lo_idx, lo_remote, me,
                jnp.maximum(me - 1, 0), L)
            ok_hi = ok_hi & ~last
            ok_lo = ok_lo & ~first
            edges = jnp.concatenate([e_hi, e_lo])              # [2H, 2]
            edge_valid = jnp.concatenate([ok_hi, ok_lo])

            # --- 4. global components over (shard, label) space ---
            gmap = global_component_map(edges, edge_valid, n_shards, L, axes)
            glab = jnp.where(own_labels >= 0,
                             gmap[me * L + jnp.maximum(own_labels, 0)],
                             -1)
        # a fresh report: never mutate the pipeline result's own report
        report = dataclasses.replace(
            res.report, halo=res.report.halo | ov1 | ov2)
        return glab, own_core, own_grid, report.as_vector()[None, :]

    spec = P(axes)
    fn = jax.shard_map(local_step, mesh=mesh,
                       in_specs=(P(axes, None), spec),
                       out_specs=(spec, spec, spec, P(axes, None)),
                       check_vma=False)

    def cluster_step(points, valid):
        labels, core, point_grid, flags = fn(points, valid)
        return (labels, core, point_grid,
                OverflowReport.from_vector(jnp.any(flags, axis=0)))

    return cluster_step


def make_staged_cluster_steps(mesh: Mesh, eps, min_pts: int,
                              caps: ClusterCaps, n_points_shard: int,
                              d: int):
    """The SPMD step as three separately-jitted stage programs.

    Same math as :func:`make_cluster_step`, but the fused program is
    split at its stage boundaries -- (1) halo exchange, (2) local
    cluster, (3) reconcile -- so a *traced* distributed fit
    (``repro.obs``) can block between dispatches and attribute
    wall-clock to each stage (ROADMAP item 2: is the 20x gap
    recompilation, halo over-exchange, or cap over-padding?).  The
    stage outputs are exactly the fused step's intermediates, so
    staged and fused fits produce identical labels / core flags /
    grids (pinned by ``tests/test_obs.py``); the split costs two extra
    dispatch round-trips plus the materialized intermediates, which is
    why the fused step remains the untraced default.

    Returns ``(halo_fn, local_fn, reconcile_fn)``, all jitted:

    * ``halo_fn(points, valid) -> (ghosts_l, ghosts_r, lo_idx, hi_idx,
      halo_overflow)``
    * ``local_fn(points, valid, ghosts_l, ghosts_r) -> (labels, core,
      point_grid, gl_labels, gl_core, gr_labels, gr_core, report_vec)``
    * ``reconcile_fn(labels, core, gl_labels, gl_core, gr_labels,
      gr_core, lo_idx, hi_idx) -> global labels``
    """
    axes = tuple(mesh.axis_names)
    n_shards = int(np.prod([mesh.shape[a] for a in axes]))
    L = caps.grit.grid_cap
    H = caps.halo_cap
    right = [(i, (i + 1) % n_shards) for i in range(n_shards)]
    left = [((i + 1) % n_shards, i) for i in range(n_shards)]

    def halo_step(pts, valid):
        me = jax.lax.axis_index(axes)
        lo_buf, lo_idx, ov1 = halo_buffer(pts, valid, eps, "lo", H)
        hi_buf, hi_idx, ov2 = halo_buffer(pts, valid, eps, "hi", H)
        ghosts_from_left = jax.lax.ppermute(hi_buf, axes, right)
        ghosts_from_right = jax.lax.ppermute(lo_buf, axes, left)
        ghosts_from_left = jnp.where(me == 0, PAD_COORD,
                                     ghosts_from_left)
        ghosts_from_right = jnp.where(me == n_shards - 1, PAD_COORD,
                                      ghosts_from_right)
        return (ghosts_from_left, ghosts_from_right, lo_idx, hi_idx,
                (ov1 | ov2)[None])

    def local_step(pts, valid, ghosts_l, ghosts_r):
        all_pts = jnp.concatenate([pts, ghosts_l, ghosts_r])
        all_valid = jnp.concatenate([
            valid,
            jnp.any(ghosts_l < PAD_COORD / 2, axis=1),
            jnp.any(ghosts_r < PAD_COORD / 2, axis=1)])
        res = device_dbscan(all_pts.astype(jnp.float32), eps, min_pts,
                            caps.grit, point_valid=all_valid)
        n_own = pts.shape[0]
        return (res.labels[:n_own], res.core[:n_own],
                res.point_grid[:n_own],
                res.labels[n_own:n_own + H], res.core[n_own:n_own + H],
                res.labels[n_own + H:], res.core[n_own + H:],
                res.report.as_vector()[None, :])

    def reconcile_step(own_labels, own_core, gl_lab, gl_core,
                       gr_lab, gr_core, lo_idx, hi_idx):
        me = jax.lax.axis_index(axes)
        first = me == 0
        last = me == n_shards - 1
        back_to_left = jnp.where(gl_core, gl_lab, -1)
        back_to_right = jnp.where(gr_core, gr_lab, -1)
        hi_remote = jax.lax.ppermute(back_to_left, axes, left)
        lo_remote = jax.lax.ppermute(back_to_right, axes, right)
        e_hi, ok_hi = shared_point_edges(
            own_labels, own_core, hi_idx, hi_remote, me,
            jnp.minimum(me + 1, n_shards - 1), L)
        e_lo, ok_lo = shared_point_edges(
            own_labels, own_core, lo_idx, lo_remote, me,
            jnp.maximum(me - 1, 0), L)
        ok_hi = ok_hi & ~last
        ok_lo = ok_lo & ~first
        edges = jnp.concatenate([e_hi, e_lo])
        edge_valid = jnp.concatenate([ok_hi, ok_lo])
        gmap = global_component_map(edges, edge_valid, n_shards, L, axes)
        return jnp.where(own_labels >= 0,
                         gmap[me * L + jnp.maximum(own_labels, 0)],
                         -1)

    s1 = P(axes)
    s2 = P(axes, None)
    halo = jax.shard_map(halo_step, mesh=mesh, in_specs=(s2, s1),
                         out_specs=(s2, s2, s1, s1, s1), check_vma=False)
    local = jax.shard_map(local_step, mesh=mesh,
                          in_specs=(s2, s1, s2, s2),
                          out_specs=(s1, s1, s1, s1, s1, s1, s1, s2),
                          check_vma=False)
    reconcile = jax.shard_map(reconcile_step, mesh=mesh,
                              in_specs=(s1,) * 8, out_specs=s1,
                              check_vma=False)
    return jax.jit(halo), jax.jit(local), jax.jit(reconcile)


# jitted SPMD steps keyed by everything that shapes the program; reused
# across distributed fits so the adaptive driver's quantized cap
# retries (and repeated runs on similarly-sized data) don't recompile.
# Fused and staged (traced) programs share the cache, disambiguated by
# the key's trailing flavor tag.
_STEP_CACHE: dict = {}
_STEP_CACHE_MAX = 32


def _step_cache_get(key, build):
    if key in _STEP_CACHE:
        # refresh insertion order: a hit is the newest entry again
        _STEP_CACHE[key] = _STEP_CACHE.pop(key)
    else:
        while len(_STEP_CACHE) >= _STEP_CACHE_MAX:
            _STEP_CACHE.pop(next(iter(_STEP_CACHE)))
        _STEP_CACHE[key] = build()
    return _STEP_CACHE[key]


def cached_cluster_step(mesh: Mesh, eps: float, min_pts: int,
                        caps: ClusterCaps, n_points_shard: int, d: int):
    key = (mesh, float(eps), int(min_pts), caps, int(n_points_shard),
           int(d), "fused")
    return _step_cache_get(
        key, lambda: jax.jit(make_cluster_step(
            mesh, eps, min_pts, caps, n_points_shard, d)))


def cached_staged_cluster_steps(mesh: Mesh, eps: float, min_pts: int,
                                caps: ClusterCaps, n_points_shard: int,
                                d: int):
    key = (mesh, float(eps), int(min_pts), caps, int(n_points_shard),
           int(d), "staged")
    return _step_cache_get(
        key, lambda: make_staged_cluster_steps(
            mesh, eps, min_pts, caps, n_points_shard, d))
