"""Grid construction (paper Algorithm 1).

Each dimension of the feature space is divided into intervals of length
``eps / sqrt(d)``; a point's grid *identifier* is the d-vector of its
interval indices (eq. (1) of the paper).  Points are then sorted
lexicographically by identifier (the paper uses radix sort; we use a
stable multi-key sort which is the vectorized equivalent) so points of
the same grid are adjacent, and the non-empty grids are read off as a
CSR partition of the sorted order.

Two implementations share the same semantics:

* ``build_grids``        -- host path (numpy, dynamic shapes): used by the
                            paper-faithful host engines and the LDF variant.
* ``build_grids_device`` -- device path (pure jnp, static ``grid_cap``):
                            jittable, feeds the distributed pipeline.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import numpy as np
import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class GridIndex:
    """CSR view of the non-empty grids over a sorted point order (host)."""

    order: np.ndarray        # [n]   permutation: points sorted by grid id
    ids: np.ndarray          # [G,d] identifiers of non-empty grids (lex-sorted)
    starts: np.ndarray       # [G]   start of each grid's points in `order`
    counts: np.ndarray       # [G]   points per grid
    point_grid: np.ndarray   # [n]   grid index (into ids) of each point, original order
    side: float              # grid side length eps/sqrt(d)
    mins: np.ndarray         # [d]   per-dim minimum used as the origin
    eta: int                 # max interval index over all dims (paper's eta)

    @property
    def num_grids(self) -> int:
        return int(self.ids.shape[0])


def identifiers(points: np.ndarray, eps: float) -> Tuple[np.ndarray, np.ndarray, float]:
    """Eq. (1): per-point grid identifiers. Returns (ids[n,d], mins[d], side)."""
    points = np.asarray(points)
    d = points.shape[1]
    side = float(eps) / np.sqrt(d)
    mins = points.min(axis=0)
    ids = np.floor((points - mins[None, :]) / side).astype(np.int64)
    return ids, mins, side


def group_rows(ids: np.ndarray):
    """Lex-sort integer id rows and read off the run (grid) structure.

    The shared core of Algorithm 1, also used by the fitted index's
    insert splice and the kernel predict's query grouping.  Returns
    ``(order, sorted_ids, starts, counts, group_of_sorted)``: a stable
    lexicographic permutation, the sorted rows, CSR boundaries of each
    run of equal rows, and each sorted row's run index.
    """
    ids = np.asarray(ids)
    n, d = ids.shape
    order = np.lexsort(tuple(ids[:, j] for j in range(d - 1, -1, -1)))
    sids = ids[order]
    new = np.ones(n, dtype=bool)
    if n:
        new[1:] = np.any(sids[1:] != sids[:-1], axis=1)
    starts = np.flatnonzero(new).astype(np.int64)
    counts = np.diff(np.append(starts, n)).astype(np.int64)
    group_of = np.cumsum(new) - 1
    return order, sids, starts, counts, group_of


def build_grids(points: np.ndarray, eps: float) -> GridIndex:
    """Algorithm 1 (host). O(n log n) via lexsort (radix-family, stable)."""
    pts = np.asarray(points, dtype=np.float64)
    n, d = pts.shape
    # n == 0 must fail *here*, not as an opaque reduction error inside
    # identifiers(); the public API (engine.cluster) validates earlier
    # still, with the same message style
    if n == 0:
        raise ValueError("empty point set")
    ids, mins, side = identifiers(pts, eps)
    order, sids, starts, counts, grid_of_sorted = group_rows(ids)
    point_grid = np.empty(n, dtype=np.int64)
    point_grid[order] = grid_of_sorted
    gids = sids[starts]
    eta = int(ids.max()) if n else 0
    return GridIndex(order=order, ids=gids, starts=starts, counts=counts,
                     point_grid=point_grid, side=side, mins=mins, eta=eta)


# --------------------------------------------------------------------------
# Device path: identical semantics, static shapes (grid_cap), pure jnp.
# --------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class DeviceGrids:
    """Static-shape grid partition living on device.

    Grids beyond ``num_grids`` are padding: ids == INT_MAX sentinel,
    counts == 0.
    """

    sorted_points: jnp.ndarray   # [n, d] points permuted to grid order
    order: jnp.ndarray           # [n]    original index of each sorted point
    ids: jnp.ndarray             # [G_cap, d] int32 identifiers (lex-sorted, padded)
    starts: jnp.ndarray          # [G_cap] int32
    counts: jnp.ndarray          # [G_cap] int32 (0 for padding)
    point_grid: jnp.ndarray      # [n] int32 grid index of each *sorted* point
    num_grids: jnp.ndarray       # [] int32
    side: jnp.ndarray            # [] f32
    mins: jnp.ndarray            # [d] f32
    overflow: jnp.ndarray        # [] bool: true grid count exceeded G_cap

    def tree_flatten(self):
        fields = (self.sorted_points, self.order, self.ids, self.starts,
                  self.counts, self.point_grid, self.num_grids, self.side,
                  self.mins, self.overflow)
        return fields, None

    @classmethod
    def tree_unflatten(cls, aux, fields):
        return cls(*fields)


PAD_ID = jnp.int32(2**30)


def build_grids_device(points: jnp.ndarray, eps, grid_cap: int) -> DeviceGrids:
    """Algorithm 1 fully in-graph. Shapes static given ``grid_cap``.

    The lexicographic radix sort of the paper maps to a stable multi-key
    ``lax.sort`` over the identifier columns.
    """
    n, d = points.shape
    side = jnp.asarray(eps, jnp.float32) / jnp.sqrt(jnp.float32(d))
    mins = points.min(axis=0)
    # Clamp identifiers into [0, PAD_ID] *before* the int32 cast:
    # padding points sit at PAD_COORD (~1e15), whose raw interval index
    # overflows int32, and XLA's out-of-range float->int conversion is
    # implementation-defined -- it may wrap negative and lex-sort the
    # padding grids *ahead of* every real grid, corrupting
    # point_grid/starts.  Clamped, every out-of-range (or non-finite)
    # coordinate lands exactly on the PAD_ID sentinel, so padding points
    # share one sentinel grid that sorts after all real grids.  A *valid*
    # point can only reach the clamp when span/side >= 2^30 -- but the
    # f32 quotient already quantizes by whole cells beyond ~2^22, so the
    # engine layer rejects span/side >= 2^22 host-side before tracing
    # (engines._check_device_grid_range); raising is impossible here
    # under jit.
    idf = jnp.floor((points - mins[None, :]) / side)
    idf = jnp.where(jnp.isfinite(idf), idf, jnp.float32(PAD_ID))
    ids = jnp.clip(idf, 0.0, jnp.float32(PAD_ID)).astype(jnp.int32)

    operands = tuple(ids[:, j] for j in range(d)) + (
        jnp.arange(n, dtype=jnp.int32),)
    sorted_ops = jax.lax.sort(operands, num_keys=d, is_stable=True)
    sids = jnp.stack(sorted_ops[:d], axis=1)          # [n, d]
    order = sorted_ops[d]
    sorted_points = points[order]

    new = jnp.concatenate([
        jnp.ones((1,), bool),
        jnp.any(sids[1:] != sids[:-1], axis=1)])      # [n]
    grid_of_sorted = (jnp.cumsum(new.astype(jnp.int32)) - 1)
    num_grids = grid_of_sorted[-1] + 1
    overflow = num_grids > grid_cap
    g = jnp.minimum(grid_of_sorted, grid_cap - 1).astype(jnp.int32)

    starts = jnp.full((grid_cap,), n, jnp.int32).at[g].min(
        jnp.arange(n, dtype=jnp.int32))
    counts = jnp.zeros((grid_cap,), jnp.int32).at[g].add(1)
    counts = jnp.where(jnp.arange(grid_cap) < num_grids, counts, 0)
    gids = jnp.full((grid_cap, d), PAD_ID, jnp.int32).at[g].set(sids)
    gids = jnp.where((jnp.arange(grid_cap) < num_grids)[:, None], gids, PAD_ID)

    return DeviceGrids(sorted_points=sorted_points, order=order, ids=gids,
                       starts=starts, counts=counts, point_grid=g,
                       num_grids=num_grids, side=side, mins=mins,
                       overflow=overflow)
