"""System behaviour: GriT-DBSCAN (all engines) vs the brute oracle."""

import numpy as np
import pytest
import jax.numpy as jnp

from repro.data.seed_spreader import seed_spreader
from repro.core.dbscan import grit_dbscan, brute_dbscan
from repro.core.device_dbscan import device_dbscan, GritCaps, PAD_COORD
from repro.core.validate import assert_dbscan_equivalent
from repro.core.grids import build_grids, build_grids_device, PAD_ID
from repro.core.labels import label_propagation


@pytest.mark.parametrize("d", [2, 3, 5, 7])
@pytest.mark.parametrize("variant", ["simden", "varden"])
def test_grit_matches_brute(d, variant):
    pts = seed_spreader(500, d, variant=variant, restarts=4, seed=d)
    eps, min_pts = 4000.0, 8
    ref = brute_dbscan(pts, eps, min_pts)
    r = grit_dbscan(pts, eps, min_pts)
    assert_dbscan_equivalent(pts, eps, min_pts, ref, r.labels)


@pytest.mark.parametrize("variant", ["grit", "ldf"])
@pytest.mark.parametrize("neighbor_engine", ["tree", "stencil"])
@pytest.mark.parametrize("merge_engine", ["fast", "center", "brute"])
def test_engine_matrix_equivalent(variant, neighbor_engine, merge_engine):
    pts = seed_spreader(400, 3, variant="varden", restarts=4, seed=7)
    eps, min_pts = 4000.0, 8
    ref = brute_dbscan(pts, eps, min_pts)
    r = grit_dbscan(pts, eps, min_pts, variant=variant,
                    neighbor_engine=neighbor_engine,
                    merge_engine=merge_engine)
    assert_dbscan_equivalent(pts, eps, min_pts, ref, r.labels)


@pytest.mark.parametrize("kind", ["permuted", "zigzag"])
def test_label_propagation_converges_on_long_chains(kind):
    """Chains whose node numbering defeats pointer jumping need O(N)
    rounds (84 and 133 at N = 256, against a log2 N + 2 = 10 round cap
    the loop once had): the labels must still be the component minima.
    Two disjoint chains plus an invalid node pin the per-component min."""
    n = 256
    rng = np.random.default_rng(0)
    if kind == "permuted":
        order = rng.permutation(n)
    else:
        order = np.concatenate([np.arange(0, n, 2), np.arange(1, n, 2)[::-1]])
    half = n // 2
    chains = [order[:half], order[half:]]
    edges = np.concatenate([np.stack([c[:-1], c[1:]], 1) for c in chains])
    node_valid = np.ones(n + 1, bool)
    node_valid[n] = False                 # isolated, invalid node
    lab = np.asarray(label_propagation(
        n + 1, jnp.asarray(edges, jnp.int32),
        jnp.ones(len(edges), bool), jnp.asarray(node_valid)))
    for c in chains:
        assert (lab[c] == c.min()).all()
    assert lab[n] == n + 1


def test_kappa_small_like_paper():
    """Paper Remark 3: kappa <= 11 in all experiments."""
    pts = seed_spreader(2000, 3, variant="varden", restarts=6, seed=1)
    r = grit_dbscan(pts, 3000.0, 10)
    assert r.stats.get("merge_max_iters", 0) <= 11


# d=3 stays in the default run; the other dims are covered nightly (the
# conformance matrix also exercises the device engine at d in {2, 3})
@pytest.mark.parametrize("d", [
    pytest.param(2, marks=pytest.mark.slow), 3,
    pytest.param(5, marks=pytest.mark.slow)])
def test_device_dbscan_matches_brute(d):
    pts = seed_spreader(512, d, variant="simden", restarts=4, seed=10 + d)
    eps, min_pts = 4000.0, 8
    ref = brute_dbscan(pts, eps, min_pts)
    caps = GritCaps(grid_cap=256, frontier_cap=256, k_cap=48, c_cap=512,
                    m_cap=512, pair_cap=2048, grid_block=64, pair_block=256)
    r = device_dbscan(jnp.asarray(pts, jnp.float32), eps, min_pts, caps)
    assert not bool(r.overflow)
    assert_dbscan_equivalent(pts, eps, min_pts, ref, np.asarray(r.labels))


@pytest.mark.slow
def test_device_dbscan_respects_point_validity():
    pts = seed_spreader(256, 2, variant="simden", restarts=3, seed=3)
    eps, min_pts = 4000.0, 8
    caps = GritCaps(grid_cap=256, frontier_cap=256, k_cap=48, c_cap=512,
                    m_cap=512, pair_cap=2048, grid_block=64, pair_block=256)
    valid = jnp.asarray(np.arange(256) < 200)
    r = device_dbscan(jnp.asarray(pts, jnp.float32), eps, min_pts, caps,
                      point_valid=valid)
    labels = np.asarray(r.labels)
    assert (labels[200:] == -1).all()
    ref = brute_dbscan(pts[:200], eps, min_pts)
    assert_dbscan_equivalent(pts[:200], eps, min_pts, ref, labels[:200])


@pytest.mark.parametrize("use_kernels", [False, True])
def test_padding_points_never_share_a_grid_with_real_ones(use_kernels):
    """Regression: identifiers of PAD_COORD rows used to go through an
    out-of-range f32->int32 cast (implementation-defined in XLA; can
    wrap negative and lex-sort padding *ahead of* real grids, corrupting
    point_grid/starts).  Clamped to the PAD_ID sentinel, every padding
    point must land in the sentinel grid, strictly after all real grids,
    and the pipeline must stay exact under a point_valid mask."""
    pts = seed_spreader(192, 2, variant="simden", restarts=3, seed=7)
    n_valid = 150
    valid = np.arange(192) < n_valid
    padded = np.where(valid[:, None], pts, PAD_COORD)

    dg = build_grids_device(jnp.asarray(padded, jnp.float32), 4000.0,
                            grid_cap=256)
    point_grid = np.asarray(dg.point_grid)
    order = np.asarray(dg.order)
    real_grids = set(point_grid[np.isin(order, np.flatnonzero(valid))])
    pad_grids = set(point_grid[np.isin(order, np.flatnonzero(~valid))])
    assert not (real_grids & pad_grids), \
        f"padding shares grids with real points: {real_grids & pad_grids}"
    # the sentinel grid must sort after every real grid and carry PAD_ID
    ids = np.asarray(dg.ids)
    assert all(g > max(real_grids) for g in pad_grids)
    assert all((ids[g] == int(PAD_ID)).all() for g in pad_grids)

    caps = GritCaps(grid_cap=256, frontier_cap=256, k_cap=48, c_cap=512,
                    m_cap=512, pair_cap=2048, grid_block=64,
                    pair_block=256, use_kernels=use_kernels)
    r = device_dbscan(jnp.asarray(pts, jnp.float32), 4000.0, 8, caps,
                      point_valid=jnp.asarray(valid))
    assert not bool(r.overflow)
    labels = np.asarray(r.labels)
    assert (labels[n_valid:] == -1).all()
    ref = brute_dbscan(pts[:n_valid], 4000.0, 8)
    assert_dbscan_equivalent(pts[:n_valid], 4000.0, 8, ref,
                             labels[:n_valid])


def test_build_grids_empty_raises_cleanly():
    """The n == 0 guard must fire before identifiers() reduces an empty
    array (it used to be unreachable)."""
    with pytest.raises(ValueError, match="empty point set"):
        build_grids(np.zeros((0, 3)), 1.0)


def test_grid_build_host_vs_device():
    pts = seed_spreader(300, 3, variant="simden", restarts=3, seed=5)
    eps = 4000.0
    gi = build_grids(pts, eps)
    dg = build_grids_device(jnp.asarray(pts, jnp.float32), eps, grid_cap=512)
    ng = int(dg.num_grids)
    assert ng == gi.num_grids
    np.testing.assert_array_equal(np.asarray(dg.ids)[:ng], gi.ids)
    np.testing.assert_array_equal(np.asarray(dg.counts)[:ng], gi.counts)


def test_all_points_in_one_ball():
    """The O(n^2)-killer case from the paper's introduction."""
    rng = np.random.default_rng(0)
    pts = rng.normal(size=(400, 3)) * 10.0
    eps = 1e5
    ref = brute_dbscan(pts, eps, 10)
    r = grit_dbscan(pts, eps, 10)
    assert_dbscan_equivalent(pts, eps, 10, ref, r.labels)
    assert r.stats["num_clusters"] == 1


def test_all_noise():
    rng = np.random.default_rng(1)
    pts = rng.uniform(0, 1e6, size=(100, 3))
    r = grit_dbscan(pts, 10.0, 5)
    assert (r.labels == -1).all()
