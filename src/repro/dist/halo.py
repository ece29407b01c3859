"""Device-side halo compaction for the slab exchange.

Each shard ships the points within 2*eps of its slab boundary to the
adjacent shard (via ``jax.lax.ppermute``).  The 2*eps width guarantees a
shipped point's own eps-neighborhood is complete on the receiving side
for any point within eps of the boundary -- the width the reconciliation
exactness argument needs (DESIGN.md §5).

The buffers are fixed-cap (``ClusterCaps.halo_cap``) so the exchange is
a static-shape collective; selection overflow is reported, never
silently truncated (the adaptive driver grows the cap and retries).
"""

from __future__ import annotations

from typing import Tuple

import numpy as np
import jax.numpy as jnp

from repro.core.device_dbscan import PAD_COORD


def halo_buffer(pts, valid, eps, side: str, cap: int):
    """Compact the points within 2*eps of the slab's dim-0 edge into a
    fixed-cap buffer.

    Args:
      pts: [n, d] shard-local points (padding rows at ``PAD_COORD``).
      valid: [n] bool.
      side: "lo" (points near the slab's min edge) or "hi" (max edge).
      cap: static buffer size.  ``cap > n`` is legal: the buffer's tail
        beyond the ``n`` selectable points is explicit padding
        (``PAD_COORD`` coordinates, index -1), and overflow can then
        never fire (at most ``n`` points are selectable).

    Returns ``(buf [cap, d] f32, idx [cap] int32 rows into pts or -1,
    overflow [] bool)``.
    """
    x0 = pts[:, 0]
    lo = jnp.min(jnp.where(valid, x0, jnp.inf))
    hi = jnp.max(jnp.where(valid, x0, -jnp.inf))
    near = valid & ((x0 <= lo + 2 * eps) if side == "lo"
                    else (x0 >= hi - 2 * eps))
    # compact the selected points into a fixed-size buffer front
    n = pts.shape[0]
    order = jnp.argsort(~near, stable=True)
    if n < cap:
        order = jnp.concatenate(
            [order, jnp.zeros((cap - n,), order.dtype)])
        sel = jnp.concatenate([near[order[:n]],
                               jnp.zeros((cap - n,), bool)])
    else:
        order = order[:cap]
        sel = near[order]
    buf = jnp.where(sel[:, None], pts[order], PAD_COORD)
    idx = jnp.where(sel, order, -1)
    overflow = jnp.sum(near) > cap
    return buf.astype(jnp.float32), idx.astype(jnp.int32), overflow


def boundary_bands(points: np.ndarray, eps: float,
                   n_shards: int) -> np.ndarray:
    """Each shard's 2*eps boundary-band populations of the slab
    partition, ``[n_shards, 2]`` (lo side, hi side): the exact
    host-side mirror of :func:`halo_buffer`'s selection predicate on
    every shard (an empty shard selects nothing).

    ``slab_cuts`` is deterministic, so the worst side
    (:func:`boundary_census`) sizes a halo cap that can never overflow
    on the fit that sized it, and the sum is the rows that fit's halo
    exchange selects over all sides (the ring ships the two outer
    sides too; their receivers mask them off)."""
    from .sharding import slab_cuts
    pts = np.asarray(points, np.float64)
    order, cut_idx, _ = slab_cuts(pts, eps, n_shards)
    starts = np.concatenate([[0], cut_idx]).astype(np.int64)
    ends = np.concatenate([cut_idx, [len(pts)]]).astype(np.int64)
    x = pts[order, 0]
    bands = np.zeros((n_shards, 2), np.int64)
    for s in range(n_shards):
        seg = x[starts[s]:ends[s]]
        if seg.size:
            bands[s] = (np.sum(seg <= seg.min() + 2 * eps),
                        np.sum(seg >= seg.max() - 2 * eps))
    return bands


def boundary_census(points: np.ndarray, eps: float, n_shards: int) -> int:
    """Worst per-side 2*eps boundary-band population of the slab
    partition (:func:`boundary_bands`' largest entry).

    A ``halo_cap >= boundary_census`` can never overflow on the fit
    that sized it -- unlike the ``halo_bound`` densest-window estimate,
    which bounds *any* window and historically left halo buffers ~76%
    padding."""
    return int(boundary_bands(points, eps, n_shards).max(initial=0))


def _quarter_pow2_at_least(x: int, lo: int = 8) -> int:
    """Smallest value >= x on the quarter-pow2 ladder (1, 1.25, 1.5,
    1.75 x 2^e): few distinct shapes like a plain pow2 bucket, but the
    over-provisioning is bounded at 25% instead of 100%, so the shared
    SPMD halo buffer is never more than a quarter slack over the worst
    boundary side."""
    x = max(int(x), lo, 8)
    e = max((x - 1).bit_length() - 1, 3)
    for m in (5, 6, 7, 8):
        v = (1 << e) * m // 4
        if v >= x:
            return v
    return 1 << (e + 1)


def census_halo_cap(points: np.ndarray, eps: float, n_shards: int,
                    lo: int = 32) -> int:
    """Halo cap sized from the actual boundary-band census (see
    :func:`boundary_census`), bucket-quantized so similarly-sized fits
    share one compiled SPMD step."""
    return halo_cap_of(boundary_bands(points, eps, n_shards), lo=lo)


def halo_cap_of(bands: np.ndarray, lo: int = 32) -> int:
    """The quantized halo cap covering the worst side of
    :func:`boundary_bands`' census."""
    return _quarter_pow2_at_least(int(np.max(bands, initial=0)), lo=lo)


def halo_census(pts_sh: np.ndarray, valid_sh: np.ndarray, eps: float,
                cap: int) -> Tuple[int, int, int]:
    """Host-side mirror of :func:`halo_buffer`'s selection predicate
    over all shards and both sides.

    Returns ``(points_selected, buffer_slots, worst_side)`` where
    ``buffer_slots = 2 * n_shards * cap`` and ``worst_side`` is the
    largest single side's selection.  It re-derives, independently of
    :func:`boundary_bands`, the rows the exchange ships -- the count
    the census behind ``dist.halo.rows`` must equal.  Pure numpy on
    the pre-packed slabs; never dispatches to the device.
    """
    pts_sh = np.asarray(pts_sh)
    valid_sh = np.asarray(valid_sh, bool)
    n_shards = pts_sh.shape[0]
    selected, worst = 0, 0
    for s in range(n_shards):
        v = valid_sh[s]
        if not v.any():
            continue
        x0 = pts_sh[s, :, 0]
        xv = x0[v]
        lo, hi = float(xv.min()), float(xv.max())
        n_lo = int(np.sum(v & (x0 <= lo + 2 * eps)))
        n_hi = int(np.sum(v & (x0 >= hi - 2 * eps)))
        selected += n_lo + n_hi
        worst = max(worst, n_lo, n_hi)
    return selected, 2 * n_shards * cap, worst
