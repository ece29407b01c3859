"""Adaptive-cap driver for the static-shape device pipeline.

The in-graph GriT pipeline (``device_dbscan``) trades the paper's dynamic
data structures for static caps; every cap carries an overflow flag.
Before this driver, callers hand-tuned ``GritCaps`` per dataset and a
missed cap silently truncated the result.  Now:

1. :func:`estimate_caps` derives an initial ``GritCaps`` from *host-side
   grid statistics*, computed while the device waits before every fit
   that estimates its caps: the non-empty-grid count bounds
   ``grid_cap``, the max grid occupancy bounds ``m_cap`` (core points
   per grid can never exceed occupancy), and :func:`stencil_census`
   walks the grid tree's levels for every grid at once to count what
   ``frontier_cap``, ``k_cap``, ``c_cap`` and ``pair_cap`` must hold.
   Both read one set of int64 level keys (:class:`_GridLevels`), built
   once per estimate.
2. :func:`adaptive_device_dbscan` runs the jitted pipeline, reads the
   per-cap :class:`OverflowReport`, geometrically grows exactly the caps
   that overflowed, and retries.  Caps are quantized to powers of two /
   block multiples so re-runs on similarly-sized data reuse the jit
   cache instead of recompiling per dataset.

Growth is geometric (default 2x), so reaching a true bound B from an
under-estimate costs O(log B) recompiles worst case; each cap is also
clamped at its provable maximum (e.g. candidates <= n, neighbors <= the
exact stencil size), so the loop terminates even on adversarial data.
"""

from __future__ import annotations

import dataclasses
import itertools
import math
from typing import List, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro import obs
from repro.core.device_dbscan import (GritCaps, DeviceDBSCANResult,
                                      OverflowReport, device_dbscan)
from repro.core.grids import identifiers
from repro.core.grid_tree import offset_stencil, radius


class CapOverflowError(RuntimeError):
    """Raised when the adaptive driver exhausts its retries."""

    def __init__(self, attempts: List[dict]):
        self.attempts = attempts
        last = attempts[-1]
        super().__init__(
            f"static caps still overflowing after {len(attempts)} "
            f"attempt(s): {last['overflow']}; last caps {last['caps']}")


def _pow2_at_least(x: int, lo: int = 1) -> int:
    return max(lo, 1 << max(int(x) - 1, 0).bit_length())


def _mult8(x: int) -> int:
    return max(8, (int(x) + 7) // 8 * 8)


@dataclasses.dataclass
class ResidentCaps:
    """Static shapes of a :class:`~repro.index.GritIndex`'s
    device-resident serving state (``index.device_state``).

    Same cap discipline as :class:`GritCaps` / ``PredictCaps``:
    power-of-two quantization so mutation-driven growth re-jits at
    O(log n) distinct shapes, monotone growth (``grown_to``), and
    never silent truncation -- the host packs the resident buffers, so
    an overflow triggers a rebuild *before* any kernel runs.
    """

    row_cap: int = 0       # physical point rows (tombstones included)
    grid_cap: int = 0      # non-empty grids
    edge_cap: int = 0      # persistent merge-graph edges

    @classmethod
    def for_state(cls, rows: int, grids: int, edges: int
                  ) -> "ResidentCaps":
        return cls(row_cap=_pow2_at_least(rows, lo=256),
                   grid_cap=_pow2_at_least(grids, lo=64),
                   edge_cap=_pow2_at_least(edges, lo=64))

    def grown_to(self, other: "ResidentCaps"
                 ) -> Tuple["ResidentCaps", bool]:
        new = ResidentCaps(row_cap=max(self.row_cap, other.row_cap),
                           grid_cap=max(self.grid_cap, other.grid_cap),
                           edge_cap=max(self.edge_cap, other.edge_cap))
        return new, new != self


def stencil_neighbor_bound(d: int) -> int:
    """Exact max number of neighboring non-empty grids: the size of the
    offset-< d stencil, minus the grid itself."""
    deltas, _ = offset_stencil(d)
    return int(len(deltas)) - 1


@dataclasses.dataclass(frozen=True)
class StencilCensus:
    """Exact host-side counts over the offset stencil of every non-empty
    grid -- what the device pipeline's stencil-shaped caps must hold."""

    candidates: int   # max own + stencil occupancy of a *small* grid
    frontier: int     # max live prefix ranges at one grid-tree level
    neighbors: int    # max non-empty stencil neighbours (self excluded)
    pairs: int        # unordered pairs of neighbouring non-empty grids


@dataclasses.dataclass(frozen=True)
class _GridLevels:
    """The non-empty grids' identifier prefixes, level by level, as
    sorted 1-D int64 keys -- the grid tree's levels, built the way the
    tree is.

    Identifiers are shifted by the stencil radius ``r`` so every probe
    ``c + delta`` stays non-negative.  Level ``j``'s key of a
    ``(j+1)``-prefix is ``rank_{j-1}(its j-prefix) * radix[j] + c_j``
    with ``radix[j] = max c_j + r + 1``, so a key, and a probe, is below
    ``G_{j-1} * radix[j]``.  With ``G <= n`` and at most 2^22 cells per
    axis (the device's identifier range) that fits int64 for every d,
    where a flat mixed-radix key over all d axes overflows at d = 5 with
    about 5,400 cells per axis.  The last level's keys are the grids
    themselves."""

    keys: Tuple[np.ndarray, ...]   # per level, sorted unique keys
    radix: Tuple[int, ...]         # per level, the multiplier of the rank
    counts: np.ndarray             # occupancy per grid (last-level order)
    r: int                         # stencil radius the ids are shifted by


def _grid_levels(points: np.ndarray, eps: float,
                 point_valid: Optional[np.ndarray] = None
                 ) -> Optional[_GridLevels]:
    """Level keys of the valid points' grids; None when none is valid."""
    pts = np.asarray(points, np.float64)
    if point_valid is not None:
        pts = pts[np.asarray(point_valid, bool)]
    if len(pts) == 0:
        return None
    r = radius(pts.shape[1])
    ids, _, _ = identifiers(pts, eps)     # non-negative per axis
    rank = np.zeros(len(pts), np.int64)
    keys, radix = [], []
    for c in (ids + r).T:
        m = int(c.max()) + r + 1
        if len(keys) and len(keys[-1]) > np.iinfo(np.int64).max // m:
            raise ValueError(
                f"grid identifier span {m} per axis is too large for "
                f"int64 level keys over {len(keys[-1])} grid prefixes")
        key, rank = np.unique(rank * m + c, return_inverse=True)
        keys.append(key)
        radix.append(m)
    return _GridLevels(keys=tuple(keys), radix=tuple(radix),
                       counts=np.bincount(rank, minlength=len(keys[-1])),
                       r=r)


def grid_stats(points: np.ndarray, eps: float,
               point_valid: Optional[np.ndarray] = None
               ) -> Tuple[int, int]:
    """(non-empty grid count, max occupancy) over the *valid* points."""
    return _occupancy(_grid_levels(points, eps, point_valid))


def _occupancy(levels: Optional[_GridLevels]) -> Tuple[int, int]:
    if levels is None:
        return 1, 1
    return int(len(levels.counts)), int(levels.counts.max())


def _census(levels: Optional[_GridLevels], min_pts: int) -> StencilCensus:
    """Walk the stencil's prefix trie depth first over the level keys.

    A trie node at depth ``j`` is a ``(j+1)``-vector ``delta`` with
    partial offset ``sum(max(|delta_i| - 1, 0)^2) < d``; its children
    extend it by one axis.  At each node every ``(j+1)``-prefix ``p`` of
    the grids probes ``p + delta`` among the level's keys:
    ``pos_{j-1}(p[:j] + delta[:j]) * radix[j] + c_j + delta_j``, taken
    only where the parent probe hit.  Siblings' probes differ only in
    ``delta_j``, so one int64 ``searchsorted`` serves all children of a
    node.  A node none of whose probes hit has no hits below it, so its
    subtree is skipped.  At most one ``(hit, pos)`` pair per level is
    alive: O(d * G) memory however large the stencil (197,067 deltas at
    d = 7)."""
    if levels is None:
        return StencilCensus(1, 1, 0, 0)
    keys, radix, counts, r = (levels.keys, levels.radix, levels.counts,
                              levels.r)
    d = len(keys)
    live = [np.zeros(len(k), np.int64) for k in keys]
    totals = np.zeros(len(counts), np.int64)

    def visit(j: int, off: int, hit: np.ndarray, pos: np.ndarray):
        parent = keys[j] // radix[j]
        base = pos[parent] * radix[j] + keys[j] % radix[j]
        parent_hit = hit[parent]
        # the children's delta_j run over -a..a; level keys are sorted
        # and unique, so the first key >= base + delta_j moves on by
        # one exactly where base + delta_j is a key
        a = next(x for x in range(r, -1, -1)
                 if off + max(x - 1, 0) ** 2 < d)
        at = np.searchsorted(keys[j], base - a)
        for dj in range(-a, a + 1):
            p = np.minimum(at, len(keys[j]) - 1)
            found = keys[j][p] == base + dj
            at += found
            h = parent_hit & found
            if not h.any():
                continue
            live[j] += h
            if j + 1 < d:
                visit(j + 1, off + max(abs(dj) - 1, 0) ** 2, h, p)
            else:
                totals[h] += counts[p[h]]

    visit(0, 0, np.ones(1, bool), np.zeros(1, np.int64))
    # the last level's prefixes are the grids themselves (self included)
    nbrs = live[-1] - 1
    small = counts < min_pts
    return StencilCensus(
        candidates=int(totals[small].max()) if small.any() else 1,
        frontier=max(1, max(int(lv.max()) for lv in live)),
        neighbors=int(nbrs.max()), pairs=int(nbrs.sum()) // 2)


def stencil_census(points: np.ndarray, eps: float, min_pts: int,
                   point_valid: Optional[np.ndarray] = None
                   ) -> StencilCensus:
    """Walk the grid tree's levels on the host, every grid at once.

    At level ``j`` the device traversal of a query grid keeps one range
    per distinct ``(j+1)``-prefix of the grid identifiers whose partial
    offset from the query's prefix is below d; the last level's ranges
    are the query's non-empty stencil cells (itself included).  Counting
    those prefixes per query gives ``frontier_cap`` and ``k_cap`` exactly
    (the device sees the same partition up to float32 identifier
    rounding), and the neighbour counts bound the core-grid merge pairs.
    ``candidates`` sums occupancies over the stencil of every grid with
    occupancy < MinPts -- a superset of the device's MinDist neighbour
    set, so it bounds every small grid's candidate total; all-core grids
    skip the candidate scan and don't constrain ``c_cap``.

    Int64 lookups of the level keys, one per node of the stencil's
    prefix trie (see :func:`_census`); the last level's lookups give
    the candidate totals too.  It runs on the host while the device
    waits, before every fit that estimates its caps."""
    return _census(_grid_levels(points, eps, point_valid), min_pts)


def _caps_from_stats(n: int, d: int, num_grids: int, max_occ: int,
                     census: StencilCensus, margin: float,
                     extra_grids: int, use_kernels: bool) -> GritCaps:
    """``GritCaps`` from grid statistics and the stencil census -- the
    quantization/clamp discipline shared by the global and the
    per-shard estimators.  ``margin`` pads the counted caps so the few
    cells float32 identifiers round differently cannot overflow them."""
    grid_cap = _pow2_at_least(
        int(math.ceil(num_grids * margin)) + extra_grids, lo=8)
    grid_block = min(64, grid_cap)

    # the census counts neighbours exactly; the offset-stencil size is
    # the provable per-grid maximum
    k_cap = _mult8(min(int(math.ceil(census.neighbors * margin)),
                       stencil_neighbor_bound(d), max(grid_cap - 1, 1)))
    frontier_cap = _pow2_at_least(int(math.ceil(census.frontier * margin)),
                                  lo=8)

    m_cap = _mult8(max_occ)
    # candidate list of a small grid: the census is the exact stencil
    # occupancy sum, an upper bound on what the device's (possibly
    # tighter) MinDist neighbor set can produce
    c_cap = _pow2_at_least(min(n, census.candidates), lo=32)

    # deduped (g < g') merge pairs are core-grid neighbour pairs: at
    # most every neighbouring pair of non-empty grids
    pair_cap = _pow2_at_least(int(math.ceil(census.pairs * margin)) + 8,
                              lo=64)
    pair_block = min(256, pair_cap)

    # paper Theorem 3: FastMerging terminates within |s_i| + |s_j|
    # iterations; lax.while_loop makes a generous bound free at runtime
    merge_iters = 2 * m_cap + 4

    return GritCaps(grid_cap=grid_cap, frontier_cap=frontier_cap,
                    k_cap=k_cap, c_cap=c_cap, m_cap=m_cap,
                    pair_cap=pair_cap, grid_block=grid_block,
                    pair_block=pair_block, merge_iters=merge_iters,
                    use_kernels=use_kernels)


def estimate_caps(points: np.ndarray, eps: float, min_pts: int,
                  point_valid: Optional[np.ndarray] = None,
                  margin: float = 1.25,
                  extra_grids: int = 2,
                  use_kernels: bool = False) -> GritCaps:
    """Initial ``GritCaps`` from host grid statistics (see module doc).

    ``extra_grids`` reserves slots for the sentinel grids that padding
    points (``point_valid == False`` -> PAD_COORD) occupy.
    ``use_kernels`` selects the kernelized distance plane; it rides on
    the caps (same static jit key) and is preserved by ``grow_caps``.
    """
    pts = np.asarray(points)
    n, d = pts.shape
    levels = _grid_levels(pts, eps, point_valid)
    num_grids, max_occ = _occupancy(levels)
    return _caps_from_stats(n, d, num_grids, max_occ,
                            _census(levels, min_pts),
                            margin, extra_grids, use_kernels)


def _shard_point_sets(points: np.ndarray, eps: float, n_shards: int):
    """The exact per-shard point set of a distributed fit: the shard's
    own slab plus the 2*eps boundary bands its neighbors ship as ghosts
    (the same selection predicate as ``repro.dist.halo.halo_buffer``)."""
    from repro.dist.sharding import slab_cuts  # deferred: dist is optional
    pts = np.asarray(points, np.float64)
    order, cut_idx, _ = slab_cuts(pts, eps, n_shards)
    starts = np.concatenate([[0], cut_idx]).astype(np.int64)
    ends = np.concatenate([cut_idx, [len(pts)]]).astype(np.int64)
    spts = pts[order]

    def ship(s: int, side: str) -> np.ndarray:
        seg = spts[starts[s]:ends[s]]
        if not len(seg):
            return seg
        x0 = seg[:, 0]
        if side == "hi":
            return seg[x0 >= x0.max() - 2 * eps]
        return seg[x0 <= x0.min() + 2 * eps]

    for s in range(n_shards):
        parts = [spts[starts[s]:ends[s]]]
        if s > 0:
            parts.append(ship(s - 1, "hi"))
        if s < n_shards - 1:
            parts.append(ship(s + 1, "lo"))
        sub = np.concatenate(parts)
        if len(sub):
            yield sub


def estimate_shard_caps(points: np.ndarray, eps: float, min_pts: int,
                        n_shards: int, margin: float = 1.25,
                        extra_grids: int = 2,
                        use_kernels: bool = False) -> GritCaps:
    """Per-shard ``GritCaps`` for the distributed fit.

    Global grid statistics are a valid but wasteful bound for the
    shard-local pipelines: slab cuts land on grid lines, so the worst
    *shard's* grid count is roughly ``1 / n_shards`` of the global one,
    yet shard-max caps derived globally inflate every shard to the
    whole dataset's table.  This runs :func:`grid_stats` /
    :func:`stencil_census` per shard over the exact per-shard point
    set (own slab + the neighbors' 2*eps ghost bands) and takes the max
    over shards -- still one shared static shape for the SPMD step,
    but sized to the worst shard instead of the union."""
    pts = np.asarray(points, np.float64)
    n, d = pts.shape
    if n_shards <= 1:
        return estimate_caps(pts, eps, min_pts, margin=margin,
                             extra_grids=extra_grids,
                             use_kernels=use_kernels)
    num_grids, max_occ, n_max = 1, 1, 1
    census = StencilCensus(1, 1, 0, 0)
    for sub in _shard_point_sets(pts, eps, n_shards):
        levels = _grid_levels(sub, eps)
        (g, o), c = _occupancy(levels), _census(levels, min_pts)
        num_grids, max_occ = max(num_grids, g), max(max_occ, o)
        n_max = max(n_max, len(sub))
        census = StencilCensus(*(max(a, b) for a, b in zip(
            dataclasses.astuple(census), dataclasses.astuple(c))))
    return _caps_from_stats(n_max, d, num_grids, max_occ, census,
                            margin, extra_grids, use_kernels)


def grow_caps(caps: GritCaps, overflowed: Tuple[str, ...], *,
              n: int, d: int, growth: float = 2.0) -> GritCaps:
    """Grow exactly the caps named in ``overflowed`` (an
    ``OverflowReport.overflowing()`` tuple), geometrically, clamped at
    each cap's provable maximum."""
    assert overflowed, "grow_caps called without any overflow"
    kw = dataclasses.asdict(caps)
    g = lambda x: int(math.ceil(x * growth))

    if "grid" in overflowed:
        kw["grid_cap"] = _pow2_at_least(g(caps.grid_cap))
    if "frontier" in overflowed:
        kw["frontier_cap"] = _pow2_at_least(
            min(g(caps.frontier_cap), kw["grid_cap"]))
    if "neighbors" in overflowed:
        kw["k_cap"] = _mult8(min(g(caps.k_cap), stencil_neighbor_bound(d)))
    if "candidates" in overflowed:
        kw["c_cap"] = min(_pow2_at_least(g(caps.c_cap)),
                          _pow2_at_least(n))
    if "core_set" in overflowed:
        kw["m_cap"] = _mult8(min(g(caps.m_cap), n))
    if "pairs" in overflowed:
        kw["pair_cap"] = _pow2_at_least(
            min(g(caps.pair_cap), kw["grid_cap"] * kw["k_cap"]))

    kw["grid_block"] = min(64, kw["grid_cap"])
    kw["pair_block"] = min(256, kw["pair_cap"])
    kw["merge_iters"] = 2 * kw["m_cap"] + 4
    new = GritCaps(**kw)
    cap_of = {"grid": "grid_cap", "frontier": "frontier_cap",
              "neighbors": "k_cap", "candidates": "c_cap",
              "core_set": "m_cap", "pairs": "pair_cap"}
    grew = any(getattr(new, cap_of[f]) > getattr(caps, cap_of[f])
               for f in overflowed if f in cap_of)
    if not grew:
        # every overflowing cap is already at its clamp -- nothing left
        # to grow; surface that instead of looping forever (drivers with
        # a retry history catch this and re-raise with the full trail)
        raise CapOverflowError(
            [{"caps": dataclasses.asdict(caps), "overflow": overflowed}])
    return new


def adaptive_loop(run, grow, describe, caps, max_retries: int):
    """The shared grow/retry protocol behind both adaptive drivers.

    ``run(caps) -> (result, OverflowReport)`` executes one attempt;
    ``grow(caps, overflowed) -> caps`` grows exactly the named caps (may
    raise :class:`CapOverflowError` at a clamp); ``describe(caps)``
    renders caps for the attempt trail.  When ``grid`` overflows, the
    flags downstream of the grid table (frontier, neighbors, candidates,
    core_set, pairs) are dropped for that round: a truncated table
    funnels the excess points into the last grid, making them unreliable
    until the grids fit.  ``halo`` is measured from the raw points and
    stays trustworthy, so it keeps growing alongside ``grid``.

    Returns (result, attempts); raises :class:`CapOverflowError` with
    the full real attempt trail on exhaustion or clamp.
    """
    attempts: List[dict] = []
    for _ in range(max_retries + 1):
        result, report = run(caps)
        overflowed = report.overflowing()
        attempts.append({"caps": describe(caps), "overflow": overflowed})
        obs.counter("adaptive.attempts").inc()
        if not overflowed:
            return result, attempts
        obs.counter("adaptive.retries").inc()
        for f in overflowed:
            obs.counter(f"adaptive.overflow.{f}").inc()
        if "grid" in overflowed:
            overflowed = tuple(f for f in overflowed
                               if f in ("grid", "halo"))
        try:
            caps = grow(caps, overflowed)
        except CapOverflowError:
            raise CapOverflowError(attempts) from None
    raise CapOverflowError(attempts)


def adaptive_device_dbscan(points, eps: float, min_pts: int,
                           caps: Optional[GritCaps] = None, *,
                           point_valid=None, max_retries: int = 8,
                           growth: float = 2.0,
                           use_kernels: Optional[bool] = None
                           ) -> Tuple[DeviceDBSCANResult, List[dict]]:
    """Run ``device_dbscan``, growing caps on overflow until exact.

    ``use_kernels`` overrides the distance plane carried by ``caps``
    (None leaves the caps' own setting -- False for estimated caps --
    untouched); the flag survives every growth round unchanged.

    Returns (result, attempts); ``attempts`` records the caps and the
    overflowing-cap names of every try (the last entry has no overflow).
    Raises :class:`CapOverflowError` if ``max_retries`` growth rounds do
    not suffice (geometric growth makes that pathological).
    """
    pts = jnp.asarray(points, jnp.float32)
    n, d = pts.shape
    if caps is None:
        with obs.stage("engine.census"):
            caps = estimate_caps(np.asarray(points), eps, min_pts,
                                 point_valid=None if point_valid is None
                                 else np.asarray(point_valid),
                                 use_kernels=bool(use_kernels))
    elif use_kernels is not None and caps.use_kernels != use_kernels:
        caps = dataclasses.replace(caps, use_kernels=use_kernels)
    tries = itertools.count()

    def run(c):
        # one dispatch through the report fetch, which waits for the
        # program: its device time is the trace's, so no histogram
        with obs.span("engine.device.attempt", attempt=next(tries)):
            res = device_dbscan(pts, eps, min_pts, c,
                                point_valid=point_valid)
            return res, jax.device_get(res.report)

    result, attempts = adaptive_loop(
        run,
        lambda c, flags: grow_caps(c, flags, n=n, d=d, growth=growth),
        dataclasses.asdict, caps, max_retries)
    # occupancy-packed dispatch telemetry (device_dbscan module doc):
    # grids actually swept per tier vs the grid_cap slots the dense
    # strategy would sweep -- the work-proportionality regression gauge
    tiers = np.asarray(jax.device_get(result.dispatch_tiers), np.int64)
    reg = obs.registry()
    for i in range(3):
        reg.gauge(f"device.dispatch.tier{i + 1}_grids").set(float(tiers[i]))
    reg.gauge("device.dispatch.dense_slots").set(float(tiers[3]))
    reg.gauge("device.dispatch.grids_swept").set(float(tiers.sum()))
    reg.gauge("device.dispatch.grid_cap").set(
        float(attempts[-1]["caps"]["grid_cap"]))
    return result, attempts
