"""Plain exact DBSCAN on an integer domain: the reference both
configurations are held to.

It imports nothing of the program and takes nothing the program made.
Coordinates are integers, so every squared distance is an exact
integer and ``d <= eps`` is decided in int64 or, inside the k-d tree,
against the radius ``sqrt(eps^2 + 1/2)``, which no integer squared
distance can straddle.

* Core flags: a point is core when at least ``min_pts`` points (itself
  included) lie within eps.  Every point of a grid cell of side
  ``eps / sqrt(d)`` holding ``min_pts`` points or more is core (the
  cell's diameter is below eps); the others are counted with a k-d tree.
* Clusters: the connected components of the core points under
  ``d <= eps``.  The core points of one cell are mutually within eps, so
  the components are found over cells.  Two cells are joined when a
  pair of their core points is within eps: a cheap witness pair (the
  extreme core points facing each other) decides most pairs, a
  bounding-box bound refutes most others, and an exact closest-pair
  search decides the rest.
* Assignment: a non-core point belongs to the cluster of its nearest
  core point within eps (any one of equally near ones); with none
  within eps it is noise.

:func:`compare_fit` counts the rows that break these rules; both of its
counts must be 0.

The control, ``ties=False``, is the same reference with the guarantee
``d <= eps`` broken at its edge: it counts only pairs strictly inside
eps (``d^2 <= eps^2 - 1`` on integers).  It must fail the comparison.
(The float steps below exact arithmetic are no control here: on
coordinates re-centred near the first operand, as the program computes,
every operand of a pair within eps is an integer below 256, exact in
bfloat16, and every sum is exact in float32.)
"""

from __future__ import annotations

import dataclasses

import numpy as np
from scipy.sparse import coo_matrix
from scipy.sparse.csgraph import connected_components
from scipy.spatial import cKDTree

WORKERS = -1


def _radius(eps2: int) -> float:
    return float(np.sqrt(eps2 + 0.5))


def _eps2(eps: float) -> int:
    e2 = float(eps) * float(eps)
    if e2 != int(e2):
        raise ValueError("the reference needs an integer eps^2")
    return int(e2)


def _check_integer(points: np.ndarray) -> None:
    if not np.array_equal(points, np.rint(points)):
        raise ValueError("the reference needs integer coordinates")


@dataclasses.dataclass
class Reference:
    """Exact DBSCAN of one table (or its control)."""

    points: np.ndarray       # [n, d] integer-valued float64
    eps2: int
    min_pts: int
    core: np.ndarray         # [n] bool
    comp: np.ndarray         # [n] int64 cluster of each core point, -1 else
    tree_core: cKDTree       # k-d tree over the core points
    core_idx: np.ndarray     # row of each tree_core entry

    def nearest_core(self, q: np.ndarray):
        """(squared distance to the nearest core point, its cluster) of
        each query; (inf, -1) where no core point lies within eps."""
        q = np.asarray(q, np.float64)
        d2 = np.full(len(q), np.inf)
        lab = np.full(len(q), -1, np.int64)
        if not len(self.core_idx):
            return d2, lab
        dist, j = self.tree_core.query(q, k=1,
                                       distance_upper_bound=_radius(
                                           self.eps2),
                                       workers=WORKERS)
        hit = np.flatnonzero(np.isfinite(dist))
        rows = self.core_idx[j[hit]]
        diff = self.points[rows] - q[hit]
        exact = (diff * diff).sum(axis=1)
        ok = exact <= self.eps2
        d2[hit[ok]] = exact[ok]
        lab[hit[ok]] = self.comp[rows[ok]]
        return d2, lab

    def labels(self) -> np.ndarray:
        """Cluster of every row: core points their own, the others that
        of their nearest core point within eps, -1 for noise."""
        out = self.comp.copy()
        nc = np.flatnonzero(~self.core)
        out[nc] = self.nearest_core(self.points[nc])[1]
        return out


def reach(d: int) -> int:
    """The largest cell offset along one axis that can hold a point
    within eps of a cell of side eps/sqrt(d)."""
    return int(np.ceil(np.sqrt(d)))


def cell_keys(cells: np.ndarray):
    """One int64 key per row of integer cell coordinates, ordered as the
    rows are lexicographically, and the per-axis strides: the key of
    ``cells + o`` is ``key + o @ strides`` for any offset ``o`` within
    :func:`reach`."""
    r = reach(cells.shape[1])
    c = np.asarray(cells, np.int64) - cells.min(axis=0) + r
    m = int(c.max()) + r + 1
    if m ** cells.shape[1] >= 2 ** 62:
        raise ValueError("too many cells per axis for an int64 key")
    strides = m ** np.arange(cells.shape[1] - 1, -1, -1, dtype=np.int64)
    return c @ strides, strides


def _cells(points: np.ndarray, eps: float):
    """Cell of side eps/sqrt(d) of every point: (cell row per point,
    sorted unique cell keys, occupancy, key strides)."""
    side = float(eps) / np.sqrt(points.shape[1])
    keys, strides = cell_keys(np.floor(points / side).astype(np.int64))
    uk, inv, cnt = np.unique(keys, return_inverse=True, return_counts=True)
    return inv.ravel(), uk, cnt, strides


def core_flags(points: np.ndarray, eps2: int, min_pts: int,
               cell_of: np.ndarray, cnt: np.ndarray,
               tree: cKDTree) -> np.ndarray:
    core = cnt[cell_of] >= min_pts
    rest = np.flatnonzero(~core)
    if len(rest):
        n_in = tree.query_ball_point(points[rest], _radius(eps2),
                                     return_length=True, workers=WORKERS)
        core[rest] = n_in >= min_pts
    return core


def _offsets(d: int) -> np.ndarray:
    """Cell offsets that can hold a point within eps of a cell of side
    eps/sqrt(d) (sum of max(|o_k| - 1, 0)^2 below d), one of each +-o
    pair."""
    r = reach(d)
    g = np.stack(np.meshgrid(*[np.arange(-r, r + 1)] * d, indexing="ij"),
                 -1).reshape(-1, d)
    g = g[(np.maximum(np.abs(g) - 1, 0) ** 2).sum(1) < d]
    nz = g != 0
    first = np.where(nz.any(1), g[np.arange(len(g)), nz.argmax(1)], 0)
    return g[first > 0]


def _seg_arg(vals: np.ndarray, starts: np.ndarray, lens: np.ndarray,
             largest: bool) -> np.ndarray:
    """Index of the first largest (or smallest) value of each
    contiguous non-empty segment."""
    ext = (np.maximum if largest else np.minimum).reduceat(vals, starts)
    at = vals == np.repeat(ext, lens)
    idx = np.where(at, np.arange(len(vals)), len(vals))
    return np.minimum.reduceat(idx, starts)


def _close_pair(a: np.ndarray, b: np.ndarray, eps2: int) -> bool:
    for s in range(0, len(a), 256):
        diff = a[s:s + 256, None, :] - b[None, :, :]
        if ((diff * diff).sum(-1) <= eps2).any():
            return True
    return False


def components(points: np.ndarray, core: np.ndarray, eps2: int,
               cell_of: np.ndarray, cell_key: np.ndarray,
               strides: np.ndarray) -> np.ndarray:
    """Cluster of every core point (-1 for the others), over cells."""
    ci = np.flatnonzero(core)
    comp = np.full(len(points), -1, np.int64)
    if not len(ci):
        return comp
    ccell = cell_of[ci]
    order = np.argsort(ccell, kind="stable")
    ci, ccell = ci[order], ccell[order]
    cp = points[ci].astype(np.int64)
    # the cells holding core points, as rows 0..m-1 of their own table
    cc, starts, lens = np.unique(ccell, return_index=True,
                                 return_counts=True)
    m = len(cc)
    lo_box = np.minimum.reduceat(cp, starts, axis=0)
    hi_box = np.maximum.reduceat(cp, starts, axis=0)
    keys = cell_key[cc]
    ea, eb, und = [], [], []
    for o in _offsets(points.shape[1]):
        probe = keys + o @ strides
        pos = np.minimum(np.searchsorted(keys, probe), m - 1)
        hit = np.flatnonzero(keys[pos] == probe)
        if not len(hit):
            continue
        a, b = hit, pos[hit]
        # witness pair: a's core point furthest along o, b's furthest
        # back along o
        proj = cp @ o
        wa = _seg_arg(proj, starts, lens, True)[a]
        wb = _seg_arg(proj, starts, lens, False)[b]
        diff = cp[wa] - cp[wb]
        yes = (diff * diff).sum(1) <= eps2
        ea.append(a[yes])
        eb.append(b[yes])
        # bounding boxes further apart than eps refute the pair
        gap = np.maximum(0, np.maximum(lo_box[b] - hi_box[a],
                                       lo_box[a] - hi_box[b]))
        maybe = ~yes & ((gap * gap).sum(1) <= eps2)
        und.append(np.stack([a[maybe], b[maybe]], 1))
    ea = np.concatenate(ea) if ea else np.zeros(0, np.int64)
    eb = np.concatenate(eb) if eb else np.zeros(0, np.int64)
    g = coo_matrix((np.ones(len(ea), np.int8), (ea, eb)), shape=(m, m))
    _, lab = connected_components(g, directed=False)
    parent = list(range(int(lab.max()) + 1))

    def find(x):
        while parent[x] != x:
            parent[x] = parent[parent[x]]
            x = parent[x]
        return x

    und = np.concatenate(und) if und else np.zeros((0, 2), np.int64)
    for a, b in und.tolist():
        ra, rb = find(lab[a]), find(lab[b])
        if ra != rb and _close_pair(cp[starts[a]:starts[a] + lens[a]],
                                    cp[starts[b]:starts[b] + lens[b]],
                                    eps2):
            parent[max(ra, rb)] = min(ra, rb)
    root = np.array([find(x) for x in range(len(parent))])
    _, cell_comp = np.unique(root[lab], return_inverse=True)
    comp[ci] = np.repeat(cell_comp.ravel(), lens)
    return comp


def fit(points: np.ndarray, eps: float, min_pts: int,
        ties: bool = True) -> Reference:
    """Exact DBSCAN of ``points``; with ``ties=False`` the control, which
    takes a pair at distance exactly eps as out of reach."""
    pts = np.asarray(points, np.float64)
    _check_integer(pts)
    eps2 = _eps2(eps) - (0 if ties else 1)
    cell_of, cell_key, cnt, strides = _cells(pts, eps)
    tree = cKDTree(pts, leafsize=32, balanced_tree=False,
                   compact_nodes=False)
    core = core_flags(pts, eps2, min_pts, cell_of, cnt, tree)
    comp = components(pts, core, eps2, cell_of, cell_key, strides)
    core_idx = np.flatnonzero(core)
    tree_core = cKDTree(pts[core_idx], leafsize=32, balanced_tree=False,
                        compact_nodes=False)
    return Reference(points=pts, eps2=eps2, min_pts=min_pts, core=core,
                     comp=comp, tree_core=tree_core, core_idx=core_idx)


def _label_map(got: np.ndarray, want: np.ndarray) -> dict:
    """Labels ``got`` matched one to one to the clusters ``want`` they
    co-occur with: each label to the cluster most of its rows have, a
    cluster claimed by two labels kept by the larger claim (-2 marks a
    label left without a cluster)."""
    pairs, counts = np.unique(np.stack([got, want], 1), axis=0,
                              return_counts=True)
    best = {}
    for (g, w), c in zip(pairs.tolist(), counts.tolist()):
        if g not in best or c > best[g][1]:
            best[g] = (w, c)
    claim = {}
    for g, (w, c) in best.items():
        if w not in claim or c > best[claim[w]][1]:
            claim[w] = g
    return {g: (w if claim[w] == g else -2) for g, (w, _) in best.items()}


def compare_fit(ref: Reference, labels: np.ndarray,
                core: np.ndarray) -> dict:
    """Rows that break exact DBSCAN: ``core_flags`` (the flag differs)
    and ``labels`` (a core point outside its cluster's label, a border
    point given no cluster of a core point within eps of it, or noise
    that is not)."""
    labels = np.asarray(labels, np.int64)
    out = {"core_flags": int((np.asarray(core, bool) != ref.core).sum())}
    ci = np.flatnonzero(ref.core)
    mapping = _label_map(labels[ci], ref.comp[ci])
    mapped = np.array([mapping[g] for g in labels[ci].tolist()], np.int64)
    n_bad = int(((labels[ci] < 0) | (mapped != ref.comp[ci])).sum())
    nc = np.flatnonzero(~ref.core)
    lists = ref.tree_core.query_ball_point(ref.points[nc],
                                           _radius(ref.eps2),
                                           workers=WORKERS) \
        if len(nc) and len(ci) else [[] for _ in nc]
    for i, cand in zip(nc.tolist(), lists):
        lab = int(labels[i])
        if not cand:
            n_bad += lab != -1
            continue
        ok = set(ref.comp[ref.core_idx[np.asarray(cand)]].tolist())
        n_bad += (lab < 0) or (mapping.get(lab, -2) not in ok)
    out["labels"] = n_bad
    return out
