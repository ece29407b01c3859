"""Operations and bytes that exact grid DBSCAN's core counting needs,
whatever implements it, and the least time a chip could take for them.

The work is the benchmark's own count, independent of the program's
tiling, caps and padding: on the GriT grid (cells of side eps/sqrt(d)
from the table's per-axis minimum), every point of a cell holding fewer
than MinPts points needs one squared distance to every point of the
non-empty cells of its stencil (the cells that can hold a point within
eps, itself included) -- the per-cell candidate total whose maximum the
program's stencil census sizes its caps by.  Cells with MinPts points or
more are all core and need none.

* operations: 3d - 1 per squared distance (d subtractions, d
  multiplications, d - 1 additions);
* bytes: each such cell's points and its stencil's points read once, as
  float32 coordinates.
"""

from __future__ import annotations

import numpy as np

from bench.reference import cell_keys, reach


def stencil(d: int) -> np.ndarray:
    """Cell offsets whose cells can hold a point within eps."""
    r = reach(d)
    g = np.stack(np.meshgrid(*[np.arange(-r, r + 1)] * d, indexing="ij"),
                 -1).reshape(-1, d)
    return g[(np.maximum(np.abs(g) - 1, 0) ** 2).sum(1) < d]


def eps_count_work(points: np.ndarray, eps: float, min_pts: int) -> dict:
    """{"distances", "flops", "bytes"} of core counting for ``points``."""
    pts = np.asarray(points, np.float64)
    n, d = pts.shape
    side = float(eps) / np.sqrt(d)
    keys, strides = cell_keys(
        np.floor((pts - pts.min(axis=0)) / side).astype(np.int64))
    keys, occ = np.unique(keys, return_counts=True)
    small = np.flatnonzero(occ < min_pts)
    cand = np.zeros(len(small), np.int64)
    for o in stencil(d):
        probe = keys[small] + o @ strides
        pos = np.minimum(np.searchsorted(keys, probe), len(keys) - 1)
        cand += np.where(keys[pos] == probe, occ[pos], 0)
    dist = int((occ[small] * cand).sum())
    return {"distances": dist, "flops": dist * (3 * d - 1),
            "bytes": int((occ[small] + cand).sum()) * d * 4}


def roofline_s(work: dict, peaks: dict):
    """(least seconds, which bound) for ``work`` on a chip with
    ``peaks`` (``flops_per_s``, ``hbm_bytes_per_s``)."""
    t_flop = work["flops"] / peaks["flops_per_s"]
    t_byte = work["bytes"] / peaks["hbm_bytes_per_s"]
    return (t_flop, "compute") if t_flop >= t_byte else (t_byte, "memory")
