"""Occupancy-packed dispatch: packed-vs-dense bit-identity + the
work-proportionality regression the packing exists for.

``GritCaps.packed`` compacts live small grids to a candidate-total
sorted prefix and sweeps occupancy-tiered buckets (c_cap/4, c_cap/2,
c_cap sub-caps) instead of ``lax.map``-ing dense ``grid_cap``-wide
blocks; the merge sweeps only the valid-pair prefix and the neighbor
table only the live-grid prefix.  All of it is required to be
*bit-identical* to the dense path -- labels, core flags, grid
provenance, cluster count, and the full ``OverflowReport`` vector --
because the dense path is the in-graph oracle the conformance matrix
pinned.  See ``device_dbscan``'s module docstring for the exactness
argument (tier width bounds candidate total; order-independent
scatters; skipped merge blocks equal their init value).
"""

import dataclasses

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro import obs
from repro.data.scenarios import default_scenarios, scenario_map
from repro.core.device_dbscan import GritCaps, device_dbscan
from repro.core.grids import build_grids_device
from repro.core.grid_tree import device_neighbor_table
from repro.engine import (adaptive_device_dbscan, stencil_census,
                          cluster, estimate_caps, estimate_shard_caps)

SCENARIOS = scenario_map()
QUICK = sorted(s.name for s in default_scenarios() if s.has("quick"))
NOT_QUICK = sorted(set(SCENARIOS) - set(QUICK))


def _both_paths(pts, eps, min_pts, caps):
    pts = jnp.asarray(np.asarray(pts, np.float32))
    dense = device_dbscan(pts, eps, min_pts,
                          caps=dataclasses.replace(caps, packed=False))
    packed = device_dbscan(pts, eps, min_pts,
                           caps=dataclasses.replace(caps, packed=True))
    return dense, packed


def _assert_bit_identical(dense, packed):
    np.testing.assert_array_equal(np.asarray(dense.labels),
                                  np.asarray(packed.labels))
    np.testing.assert_array_equal(np.asarray(dense.core),
                                  np.asarray(packed.core))
    np.testing.assert_array_equal(np.asarray(dense.point_grid),
                                  np.asarray(packed.point_grid))
    assert int(dense.num_clusters) == int(packed.num_clusters)
    assert bool(dense.overflow) == bool(packed.overflow)
    np.testing.assert_array_equal(np.asarray(dense.report.as_vector()),
                                  np.asarray(packed.report.as_vector()))


# ---------------------------------------------------------------------------
# parity: scenario catalogue
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", QUICK)
@pytest.mark.parametrize("use_kernels", [False, True],
                         ids=["naive", "kernels"])
def test_packed_parity_quick(name, use_kernels):
    sc = SCENARIOS[name]
    pts = sc.points()
    caps = estimate_caps(np.asarray(pts, np.float32), sc.eps, sc.min_pts,
                         use_kernels=use_kernels)
    _assert_bit_identical(*_both_paths(pts, sc.eps, sc.min_pts, caps))


@pytest.mark.slow
@pytest.mark.parametrize("name", NOT_QUICK)
def test_packed_parity_full_catalogue(name):
    sc = SCENARIOS[name]
    pts = sc.points()
    caps = estimate_caps(np.asarray(pts, np.float32), sc.eps, sc.min_pts)
    _assert_bit_identical(*_both_paths(pts, sc.eps, sc.min_pts, caps))


# ---------------------------------------------------------------------------
# parity: adversarial occupancy skew
# ---------------------------------------------------------------------------

def test_packed_parity_one_huge_grid_many_singletons():
    """Worst tier skew: one grid holding half the points (all-core
    shortcut) surrounded by a sea of singleton grids (all tier 1)."""
    rng = np.random.default_rng(7)
    eps, min_pts = 4.0, 5
    dense_blob = rng.uniform(0, 1.0, size=(400, 2))
    singles = np.stack([rng.permutation(300) * 50.0 + 500.0,
                        rng.uniform(0, 1e4, 300)], axis=1)
    pts = np.concatenate([dense_blob, singles]).astype(np.float32)
    caps = estimate_caps(pts, eps, min_pts)
    _assert_bit_identical(*_both_paths(pts, eps, min_pts, caps))


def test_packed_parity_all_grids_at_min_pts_minus_one():
    """Every grid exactly at occupancy min_pts - 1: no all-core
    shortcut fires anywhere, every live grid goes through the tiered
    candidate sweep, and core status hinges on cross-grid counts."""
    rng = np.random.default_rng(11)
    eps, min_pts = 3.0, 4
    side = eps / np.sqrt(2.0)
    cells = np.stack(np.meshgrid(np.arange(12), np.arange(12)),
                     -1).reshape(-1, 2) * side
    pts = np.concatenate([
        c + rng.uniform(0.1 * side, 0.9 * side, size=(min_pts - 1, 2))
        for c in cells]).astype(np.float32)
    caps = estimate_caps(pts, eps, min_pts)
    _assert_bit_identical(*_both_paths(pts, eps, min_pts, caps))


def test_packed_parity_on_candidate_overflow():
    """A grid whose candidate total exceeds c_cap must raise the same
    candidates flag on both paths (the packed path derives it from the
    global totals, not from the widest tier's truncation)."""
    rng = np.random.default_rng(3)
    pts = np.asarray(rng.uniform(0, 4.0, size=(300, 2)), np.float32)
    eps, min_pts = 1.5, 200
    caps = estimate_caps(pts, eps, min_pts)
    caps = dataclasses.replace(caps, c_cap=32)   # force truncation
    dense, packed = _both_paths(pts, eps, min_pts, caps)
    assert bool(dense.report.candidates)
    _assert_bit_identical(dense, packed)


def test_packed_parity_pair_cap_exceeding_pair_universe():
    """pair_cap > grid_cap * k_cap pads the compacted pair prefix back
    up to the cap instead of crashing the block reshape."""
    rng = np.random.default_rng(5)
    pts = np.asarray(rng.uniform(0, 30.0, size=(120, 2)), np.float32)
    eps, min_pts = 4.0, 3
    caps = estimate_caps(pts, eps, min_pts)
    caps = dataclasses.replace(
        caps, grid_cap=64, grid_block=8, k_cap=8, pair_cap=1024,
        pair_block=256)
    _assert_bit_identical(*_both_paths(pts, eps, min_pts, caps))


def test_neighbor_table_packed_parity():
    rng = np.random.default_rng(13)
    pts = jnp.asarray(rng.uniform(0, 200.0, (500, 3)), jnp.float32)
    dg = build_grids_device(pts, 9.0, 1024)
    dense = device_neighbor_table(dg.ids, dg.num_grids, frontier_cap=64,
                                  k_cap=64, packed=False)
    packed = device_neighbor_table(dg.ids, dg.num_grids, frontier_cap=64,
                                   k_cap=64, packed=True)
    for a, b in zip(dense, packed):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# work proportionality: dispatch must scale with live grids, not caps
# ---------------------------------------------------------------------------

def test_dispatch_scales_with_live_grids_not_grid_cap():
    """The regression the packing exists for: with grid_cap >> live
    grids, the packed sweep must visit ~num_grids rows, not grid_cap
    slots.  Asserted through the repro.obs dispatch gauges (recorded
    even with tracing off)."""
    rng = np.random.default_rng(17)
    pts = np.asarray(rng.uniform(0, 100.0, size=(400, 2)), np.float32)
    eps, min_pts = 5.0, 4
    caps = estimate_caps(pts, eps, min_pts)
    big = dataclasses.replace(caps, grid_cap=4096, grid_block=64,
                              pair_cap=65536)
    adaptive_device_dbscan(jnp.asarray(pts), eps, min_pts, big)
    snap = obs.registry().snapshot()
    swept = snap["device.dispatch.grids_swept"]["value"]
    cap = snap["device.dispatch.grid_cap"]["value"]
    assert cap == 4096.0
    assert snap["device.dispatch.dense_slots"]["value"] == 0.0
    # every live small grid is swept exactly once; the dead ~3700 slots
    # are never dispatched
    assert 0 < swept <= 400
    assert swept < cap / 4


def test_dense_path_reports_dense_slots():
    rng = np.random.default_rng(19)
    pts = np.asarray(rng.uniform(0, 100.0, size=(200, 2)), np.float32)
    caps = estimate_caps(pts, 5.0, 4)
    caps = dataclasses.replace(caps, packed=False)
    adaptive_device_dbscan(jnp.asarray(pts), 5.0, 4, caps)
    snap = obs.registry().snapshot()
    assert snap["device.dispatch.dense_slots"]["value"] == caps.grid_cap
    assert snap["device.dispatch.grids_swept"]["value"] == caps.grid_cap


# ---------------------------------------------------------------------------
# caps validation + snapshot round-trip of the packed flag
# ---------------------------------------------------------------------------

def test_grid_block_divisibility_validated():
    with pytest.raises(ValueError, match=r"grid_cap \(100\).*grid_block"):
        GritCaps(grid_cap=100, grid_block=64)
    with pytest.raises(ValueError, match=r"grid_block"):
        GritCaps(grid_block=0)


def test_pair_block_divisibility_validated():
    with pytest.raises(ValueError, match=r"pair_cap \(1000\).*pair_block"):
        GritCaps(pair_cap=1000, pair_block=256)
    with pytest.raises(ValueError, match=r"pair_block"):
        GritCaps(pair_block=-8)


def test_snapshot_round_trips_packed_flag():
    from repro.index import GritIndex, fit_index
    rng = np.random.default_rng(23)
    pts = rng.uniform(0, 50.0, size=(150, 2))
    for packed in (True, False):
        caps = dataclasses.replace(
            estimate_caps(np.asarray(pts, np.float32), 4.0, 4),
            packed=packed)
        idx = fit_index(pts, 4.0, 4, engine="device", caps=caps)
        restored = GritIndex.restore(idx.snapshot())
        assert restored.caps.packed is packed


def test_restore_accepts_pre_packed_snapshots():
    """10-slot caps arrays (pre-packed-dispatch snapshots) restore with
    packed defaulting on."""
    from repro.index import GritIndex, fit_index
    rng = np.random.default_rng(29)
    pts = rng.uniform(0, 50.0, size=(150, 2))
    caps = estimate_caps(np.asarray(pts, np.float32), 4.0, 4)
    idx = fit_index(pts, 4.0, 4, engine="device", caps=caps)
    snap = dict(idx.snapshot())
    assert len(snap["caps"]) == 11
    snap["caps"] = snap["caps"][:10]
    assert GritIndex.restore(snap).caps.packed is True


# ---------------------------------------------------------------------------
# census-sized caps (tentpole b): exactness of the host-side bounds
# ---------------------------------------------------------------------------

def test_candidate_census_bounds_device_totals():
    """The census is the stencil occupancy sum -- an upper bound on the
    device's (MinDist-pruned) per-grid candidate totals -- plus the
    grid tree's exact per-level frontier and neighbour counts, so
    census-sized caps never overflow on the fit that sized them."""
    rng = np.random.default_rng(31)
    pts = np.asarray(rng.uniform(0, 60.0, size=(600, 2)), np.float32)
    eps, min_pts = 4.0, 6
    census = stencil_census(pts, eps, min_pts)
    caps = estimate_caps(pts, eps, min_pts)
    assert caps.c_cap >= census.candidates
    assert caps.k_cap >= census.neighbors
    assert caps.frontier_cap >= census.frontier
    res = device_dbscan(jnp.asarray(pts), eps, min_pts, caps)
    assert jax.device_get(res.report).overflowing() == ()


def test_estimate_shard_caps_not_inflated_to_global():
    """On spread-out data the per-shard caps must come in under the
    global ones (the point of sizing per shard), while single-shard
    estimation degenerates to the global estimate."""
    rng = np.random.default_rng(37)
    pts = rng.uniform(0, 4000.0, size=(4000, 2))
    eps, min_pts = 20.0, 5
    g = estimate_caps(np.asarray(pts, np.float32), eps, min_pts)
    s = estimate_shard_caps(pts, eps, min_pts, n_shards=4)
    assert s.grid_cap <= g.grid_cap
    assert s.pair_cap <= g.pair_cap
    assert estimate_shard_caps(pts, eps, min_pts, n_shards=1) == g


def test_boundary_census_bounds_halo_cap():
    from repro.dist import boundary_census, census_halo_cap
    rng = np.random.default_rng(41)
    pts = rng.uniform(0, 1000.0, size=(3000, 2))
    worst = boundary_census(pts, 15.0, 4)
    cap = census_halo_cap(pts, 15.0, 4)
    assert cap >= worst
    # quarter-pow2 ladder: over-provisioning bounded at 25% of the
    # worst boundary side
    assert cap <= max(1.25 * worst, 32)


def test_quarter_pow2_ladder():
    from repro.dist.halo import _quarter_pow2_at_least
    for x in (1, 8, 9, 100, 545, 1000, 4097):
        v = _quarter_pow2_at_least(x)
        assert v >= max(x, 8)
        # over-provisioning bounded at 25% of the (floor-clamped) census
        assert v <= 1.25 * max(x, 8)
    assert _quarter_pow2_at_least(545) == 640


# ---------------------------------------------------------------------------
# deprecation shim
# ---------------------------------------------------------------------------

def test_core_distributed_shim_warns():
    """The pre-dist-package home stays importable behind a
    DeprecationWarning pointing at repro.dist (the repro.index.insert
    treatment)."""
    import importlib
    import sys

    sys.modules.pop("repro.core.distributed", None)
    with pytest.warns(DeprecationWarning, match=r"repro\.dist"):
        shim = importlib.import_module("repro.core.distributed")
    import repro.dist as dist
    assert shim.distributed_fit is dist.distributed_fit
    assert shim.ClusterCaps is dist.ClusterCaps


def test_packed_is_default_and_matches_dense_end_to_end():
    """``packed`` defaults on, and the public engine entry point yields
    dense-path labels bit-for-bit under either strategy."""
    assert GritCaps().packed is True
    rng = np.random.default_rng(43)
    pts = rng.uniform(0, 80.0, size=(500, 2))
    eps, min_pts = 5.0, 5
    caps = estimate_caps(np.asarray(pts, np.float32), eps, min_pts)
    res = cluster(pts, eps, min_pts, engine="device", caps=caps)
    snap = obs.registry().snapshot()
    assert snap["device.dispatch.dense_slots"]["value"] == 0.0
    ref = cluster(pts, eps, min_pts, engine="device",
                  caps=dataclasses.replace(caps, packed=False))
    np.testing.assert_array_equal(np.asarray(res.labels),
                                  np.asarray(ref.labels))
