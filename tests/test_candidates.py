"""``_candidates_for_grids`` against a plain numpy reference.

The packed and dense fit paths both build their candidate lists with
this one function, so their parity tests cannot see a fault in it.  The
reference walks each row's stencil in table order -- own grid first,
then the neighbour columns, ``-1`` skipped -- and lists every point;
slots past the list are invalid, with point index 0 and the grid of the
stencil's last column (``-1`` read as grid 0).  The function must give
it exactly, on every slot, from small stencils up to K = 300.
"""

import types

import numpy as np
import pytest
import jax.numpy as jnp

from repro.core.device_dbscan import _candidates_for_grids

B = 12


def _reference(counts, starts, nbr, gsel, width):
    K = nbr.shape[1]
    idx = np.zeros((len(gsel), width), np.int32)
    grid = np.zeros((len(gsel), width), np.int32)
    valid = np.zeros((len(gsel), width), bool)
    total = np.zeros(len(gsel), np.int32)
    for b, g in enumerate(gsel):
        stencil = [g] + list(nbr[g])
        points = [(starts[s] + j, s) for s in stencil if s >= 0
                  for j in range(counts[s])]
        total[b] = len(points)
        last = stencil[K] if stencil[K] >= 0 else 0
        for slot in range(width):
            if slot < len(points):
                idx[b, slot], grid[b, slot] = points[slot]
                valid[b, slot] = True
            else:
                grid[b, slot] = last
    return idx, grid, valid, total


def _table(width, K, seed):
    """Rows 0..B-1 are the selected grids, their stencils drawn from a pool
    of other grids.  Rows 0-4 pin the edges: an empty own grid, no
    neighbours, total == width, total > width (truncated), total 0."""
    rng = np.random.default_rng(seed)
    pool = 4 * K
    G = B + pool
    counts = rng.poisson(max(width / (1.4 * K), 0.3), G).astype(np.int32)
    nbr = np.full((G, K), -1, np.int32)
    nbr[:B] = rng.integers(B, G, (B, K))
    nbr[:B][rng.random((B, K)) < 0.3] = -1
    nbr[1] = -1
    nbr[4] = -1
    counts[0] = counts[4] = 0
    counts[1] = min(width // 2 + 1, 64)
    for b, target in ((2, width), (3, width + width // 2 + 1)):
        for k in range(K - 1, -1, -1):
            if counts[nbr[b][nbr[b] >= 0]].sum() <= target:
                break
            nbr[b, k] = -1
        counts[b] = target - counts[nbr[b][nbr[b] >= 0]].sum()
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]]).astype(np.int32)
    gsel = rng.permutation(B).astype(np.int32)
    return counts, starts, nbr, gsel


@pytest.mark.parametrize("K", [8, 26, 62, 120, 124, 300])
@pytest.mark.parametrize("width", [8, 1024, 4096])
def test_candidates_match_reference(width, K):
    counts, starts, nbr, gsel = _table(width, K, seed=width + K)
    want = _reference(counts, starts, nbr, gsel, width)
    totals = want[3]
    assert totals[list(gsel).index(2)] == width
    assert totals[list(gsel).index(3)] > width
    dg = types.SimpleNamespace(counts=jnp.asarray(counts),
                               starts=jnp.asarray(starts))
    got = _candidates_for_grids(dg, jnp.asarray(nbr), jnp.asarray(gsel),
                                width)
    for name, g, w in zip(("cand_idx", "cand_grid", "cand_valid",
                           "cand_total"), got, want):
        assert g.dtype == w.dtype, name
        np.testing.assert_array_equal(np.asarray(g), w, err_msg=name)
