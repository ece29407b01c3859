"""The host grid census that sizes the device fit's caps.

``grid_stats`` and ``stencil_census`` walk the grid tree's levels on
int64 level keys (``engine/adaptive.py``).  Their results must be the
exact counts, not bounds: the caps, and so the compiled fit program,
are derived from them.  Each case here compares them with a plain
O(G^2) reference over pairs of non-empty grids, written below from the
definitions alone.
"""

import dataclasses
import math
import tracemalloc
import zlib

import numpy as np
import pytest

from repro.core.grid_tree import offset_stencil, radius
from repro.data.seed_spreader import seed_spreader
from repro.engine import (estimate_caps, estimate_shard_caps, grid_stats,
                          stencil_census)
from repro.engine.adaptive import (StencilCensus, _caps_from_stats,
                                   _shard_point_sets)
from repro.engine.engines import _check_device_grid_range


def _offsets(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Partial offsets sum(max(|delta| - 1, 0)^2) of every row of a from b."""
    return (np.maximum(np.abs(a - b) - 1, 0) ** 2).sum(-1)


def _reference(points, eps, min_pts, point_valid=None):
    """((grid count, max occupancy), StencilCensus) by brute force."""
    pts = np.asarray(points, np.float64)
    if point_valid is not None:
        pts = pts[np.asarray(point_valid, bool)]
    if not len(pts):
        return (1, 1), StencilCensus(1, 1, 0, 0)
    d = pts.shape[1]
    ids = np.floor((pts - pts.min(0)) / (eps / np.sqrt(d))).astype(np.int64)
    grids, counts = np.unique(ids, axis=0, return_counts=True)
    frontier = 1
    for j in range(d):
        pre = np.unique(grids[:, :j + 1], axis=0)
        frontier = max(frontier, max(int((_offsets(pre, p) < d).sum())
                                     for p in pre))
    nbr = [_offsets(grids, g) < d for g in grids]
    live = np.array([m.sum() for m in nbr]) - 1
    cand = max((int(counts[m].sum()) for m, c in zip(nbr, counts)
                if c < min_pts), default=1)
    census = StencilCensus(candidates=cand, frontier=frontier,
                           neighbors=int(live.max()),
                           pairs=int(live.sum()) // 2)
    return (len(grids), int(counts.max())), census


def _blobs(rng, d, side, centers, n_per, extent):
    """n_per points uniform in a box of ``extent`` cells about each
    center (given in cells)."""
    return np.concatenate([
        np.asarray(c, np.float64) * side
        + rng.uniform(0, extent * side, (n_per, d)) for c in centers])


def _table(name: str):
    """(points, eps, min_pts, point_valid) of one census case."""
    rng = np.random.default_rng(zlib.crc32(name.encode()))
    if name.startswith("d") and name[1:].isdigit():
        d = int(name[1:])
        eps = 10.0
        side = eps / np.sqrt(d)
        centers = rng.integers(0, 12, (4, d))
        pts = np.concatenate([
            _blobs(rng, d, side, centers, 150, 5),
            rng.uniform(0, 20 * side, (60, d))])
        return pts, eps, 6, None
    if name == "negative-offset":
        d, eps = 3, 7.0
        side = eps / np.sqrt(d)
        pts = _blobs(rng, d, side, [(-9000, -9000, -9000),
                                    (-8990, -8995, -9003)], 400, 8)
        return pts - 123.456, eps, 5, None
    if name == "padded":
        d, eps = 2, 5.0
        side = eps / np.sqrt(d)
        real = _blobs(rng, d, side, [(3, 4), (30, 2), (10, 20)], 300, 6)
        padded = np.zeros((1024, d), np.float32)
        padded[:len(real)] = real
        return padded, eps, 8, np.arange(1024) < len(real)
    if name == "all-invalid":
        return (rng.uniform(0, 100, (64, 3)), 10.0, 4,
                np.zeros(64, bool))
    if name == "d3-near-device-limit":
        d, eps = 3, 1.0
        side = eps / np.sqrt(d)
        far = 2 ** 22 - 40
        pts = _blobs(rng, d, side, [(0, 0, 0), (far, 7, far // 2),
                                    (far - 3, 5, far // 2 + 2)], 200, 6)
        return pts, eps, 10, None
    if name == "d7-flat-key-overflow":
        d, eps = 7, 1.0
        side = eps / np.sqrt(d)
        centers = [np.zeros(d), np.full(d, 600),
                   rng.integers(0, 600, d), rng.integers(0, 600, d)]
        pts = np.concatenate([_blobs(rng, d, side, centers[:2], 60, 3),
                              _blobs(rng, d, side, centers[2:], 40, 4)])
        return pts, eps, 3, None
    raise KeyError(name)


CASES = ["d1", "d2", "d3", "d4", "d5", "negative-offset", "padded",
         "all-invalid", "d3-near-device-limit", "d7-flat-key-overflow"]


@pytest.mark.parametrize("name", CASES)
def test_census_matches_reference(name):
    pts, eps, min_pts, valid = _table(name)
    (num_grids, max_occ), census = _reference(pts, eps, min_pts, valid)
    assert grid_stats(pts, eps, valid) == (num_grids, max_occ)
    assert stencil_census(pts, eps, min_pts, valid) == census
    if name == "all-invalid":
        assert census == StencilCensus(1, 1, 0, 0)
    else:
        assert census.pairs > 0 and census.candidates > 1


def test_census_cases_reach_the_key_limits():
    """The two extreme cases sit where a careless key breaks: the d = 3
    span is just inside the device's 2^22 cells per axis, and the
    d = 7 span makes a flat mixed-radix key overflow int64."""
    pts, eps, _, _ = _table("d3-near-device-limit")
    _check_device_grid_range(pts, eps)
    span = (pts.max(0) - pts.min(0)).max() / (eps / np.sqrt(3))
    assert 2 ** 21 < span < 2 ** 22
    pts, eps, _, _ = _table("d7-flat-key-overflow")
    ids = np.floor((pts - pts.min(0)) / (eps / np.sqrt(7))).astype(np.int64)
    r = radius(7)
    assert math.prod(int(m) + 2 * r + 1 for m in ids.max(0)) > 2 ** 63


def test_census_memory_is_linear_in_grids():
    """The census walks the stencil's prefix trie depth first, so its
    memory is O(d * G), not O(|stencil| * G): at d = 7 the stencil
    holds 197,067 deltas."""
    pts, eps, min_pts, _ = _table("d7-flat-key-overflow")
    g, _ = grid_stats(pts, eps)
    d = pts.shape[1]
    tracemalloc.start()
    try:
        stencil_census(pts, eps, min_pts)
        _, peak = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    assert peak < 16 * d * g * 8
    assert peak * 1000 < len(offset_stencil(d)[0]) * g * 8


def test_estimated_caps_equal_reference_sized_caps():
    """``estimate_caps`` and the four-shard ``estimate_shard_caps`` on a
    seed-spreader table size every cap from the exact census."""
    pts = seed_spreader(20000, 3, r_vicinity=400.0, seed=3)
    eps, min_pts = 250.0, 20
    (num_grids, max_occ), census = _reference(pts, eps, min_pts)
    assert estimate_caps(pts, eps, min_pts) == _caps_from_stats(
        len(pts), 3, num_grids, max_occ, census, 1.25, 2, False)
    shards = [(len(sub),) + stats + dataclasses.astuple(c)
              for sub in _shard_point_sets(pts, eps, 4)
              for stats, c in [_reference(sub, eps, min_pts)]]
    n_max, num_grids, max_occ, *census = (int(x) for x in
                                          np.max(shards, axis=0))
    assert len(shards) == 4
    assert estimate_shard_caps(pts, eps, min_pts, n_shards=4) == \
        _caps_from_stats(n_max, 3, num_grids, max_occ,
                         StencilCensus(*census), 1.25, 2, False)


def test_census_refuses_keys_beyond_int64():
    """A span whose level keys would not fit int64 is refused, never
    wrapped round into wrong counts."""
    eps = 1.0
    far = 2.0 ** 62 * eps / np.sqrt(2)
    pts = np.array([[0.0, 0.0], [far, far]])
    with pytest.raises(ValueError, match="int64 level keys"):
        stencil_census(pts, eps, 2)
