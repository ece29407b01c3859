"""Fitted-index conformance: ``GritIndex.predict`` must equal the
brute-oracle assignment rule on every serving scenario, ``insert``
followed by a label read-out must be label-equivalent (canonicalized,
contested borders excepted) to a from-scratch ``cluster()`` on the
union set, and ``snapshot``/``restore`` must round-trip bit-exactly.

The oracle assignment rule: a query is noise iff no core point of the
fitted set lies within eps; otherwise it takes the label of the nearest
core point (ties: any label at the minimal distance is accepted --
engines may break exact-distance ties either way).
"""

import io

import numpy as np
import pytest

from repro.core.dbscan import brute_dbscan
from repro.core.grids import GridIndex, identifiers
from repro.core.validate import assert_labels_conformant, core_flags
from repro.data.scenarios import (get_serving_scenario, serving_scenarios,
                                  scenario_map)
from repro.engine import cluster
from repro.index import GritIndex, fit_index

SERVING = sorted(s.name for s in serving_scenarios())


@pytest.fixture(scope="module")
def fitted():
    """One fitted index + oracle per serving scenario (module memo)."""
    cache = {}

    def get(name):
        if name not in cache:
            ss = get_serving_scenario(name)
            pts = ss.fit_points()
            res = cluster(pts, ss.base.eps, ss.base.min_pts, engine="grit",
                          return_index=True)
            cache[name] = (ss, pts, res)
        return cache[name]

    return get


def _oracle_assign(pts, core, labels, queries, eps):
    """Reference assignment: (labels, set-of-valid-labels-per-query)."""
    cpts = pts[core]
    clab = np.asarray(labels)[core]
    eps2 = float(eps) ** 2
    out = np.full(len(queries), -1, np.int64)
    valid = []
    for i, q in enumerate(queries):
        d2 = ((cpts - q) ** 2).sum(axis=1)
        j = d2.argmin()
        if d2[j] <= eps2:
            cand = set(clab[d2 == d2[j]].tolist())
            out[i] = clab[j]
            valid.append(cand)
        else:
            valid.append({-1})
    return out, valid


# --------------------------------------------------------------------------
# predict
# --------------------------------------------------------------------------

@pytest.mark.parametrize("name", SERVING)
def test_predict_matches_oracle_rule_host(name, fitted):
    """Acceptance: predict == brute-oracle assignment for every query
    scenario (near-cluster, empty-grid, outside-the-box, exact-eps)."""
    ss, pts, res = fitted(name)
    q = ss.query_batch()
    got = res.index.predict(q, mode="host")
    ref, valid = _oracle_assign(pts, res.core, res.labels, q, ss.base.eps)
    for i in range(len(q)):
        assert got[i] in valid[i], \
            f"query {i}: predicted {got[i]}, oracle allows {valid[i]}"
    # noise sets must agree exactly (no tie ambiguity there)
    np.testing.assert_array_equal(got == -1, ref == -1)


@pytest.mark.parametrize("name", SERVING)
def test_predict_kernel_mode_matches_host(name, fitted):
    """The slot-batched jitted path agrees with the float64 host path
    away from the knife edge (float32 can legitimately flip queries
    within ~1e-6 relative of eps; the scenario places only its
    deliberate exact-boundary queries there)."""
    ss, pts, res = fitted(name)
    q = ss.query_batch()
    idx = res.index
    host = idx.predict(q, mode="host")
    stats = {}
    kern = idx.predict(q, mode="kernel", stats=stats)
    assert stats["mode"] == "kernel" and stats["groups"] >= 1
    # mask out queries at the f32 knife edge of the eps ball
    cpts = pts[np.asarray(res.core)]
    eps = ss.base.eps
    decidable = np.ones(len(q), bool)
    for i, qq in enumerate(q):
        dmin = np.sqrt(((cpts - qq) ** 2).sum(axis=1).min())
        decidable[i] = abs(dmin - eps) > 1e-5 * eps
    np.testing.assert_array_equal(host[decidable], kern[decidable])


def test_predict_kernel_mode_split_calls_match_one_call(fitted,
                                                        monkeypatch):
    """A batch whose candidate slots exceed ``PREDICT_SLOTS`` runs as
    several kernel calls of one shape; the answers are those of the
    single call."""
    from repro.index import grit_index
    ss, pts, res = fitted("query-heavy-3d")
    idx = res.index
    q = ss.query_batch()
    one = {}
    want = idx.predict(q, mode="kernel", stats=one)
    assert one["chunks"] == 1
    monkeypatch.setattr(grit_index, "PREDICT_SLOTS", idx.predict_caps.cand_cap)
    split = {}
    got = idx.predict(q, mode="kernel", stats=split)
    assert split["chunks"] == split["groups"] > 1
    np.testing.assert_array_equal(got, want)


def test_predict_empty_grid_and_far_queries(fitted):
    ss, pts, res = fitted("query-heavy-3d")
    idx = res.index
    rng = np.random.default_rng(3)
    far = rng.uniform(-5e5, -2e5, size=(16, idx.d))     # far outside
    np.testing.assert_array_equal(idx.predict(far), np.full(16, -1))
    # empty interior cell: a fitted core point's label must be its own
    core_i = int(np.flatnonzero(res.core)[0])
    assert idx.predict(pts[core_i:core_i + 1])[0] == res.labels[core_i]


def test_predict_exact_eps_boundary_is_inside(fitted):
    """d(q, core) exactly == eps (as f64 evaluates it) must label the
    query (DBSCAN's <=), bit-identically to the oracle formula."""
    ss, pts, res = fitted("drift-2d")
    idx = res.index
    core_idx = np.flatnonzero(res.core)[:8]
    eps = ss.base.eps
    for ci in core_idx:
        q = pts[ci].copy()
        q[0] += eps
        d2 = ((pts[np.asarray(res.core)] - q) ** 2).sum(axis=1).min()
        want = idx.predict(q[None, :], mode="host")[0]
        if d2 <= eps ** 2:
            assert want >= 0
        else:
            # f64 rounding pushed the constructed point just outside;
            # the oracle must agree that it is noise
            assert want == -1


def test_predict_validates_inputs(fitted):
    _, _, res = fitted("drift-2d")
    with pytest.raises(ValueError, match="queries must be"):
        res.index.predict(np.zeros((3, 5)))
    bad = np.zeros((2, 2))
    bad[1, 0] = np.nan
    with pytest.raises(ValueError, match="non-finite"):
        res.index.predict(bad)
    assert res.index.predict(np.zeros((0, 2))).shape == (0,)


def test_predict_caps_grow_monotonically(fitted):
    ss, pts, res = fitted("drift-2d")
    idx = res.index
    s1, s2 = {}, {}
    idx.predict(ss.query_batch(n=16), mode="kernel", stats=s1)
    caps1 = idx.predict_caps
    idx.predict(ss.query_batch(n=120), mode="kernel", stats=s2)
    caps2 = idx.predict_caps
    assert caps2.group_cap >= caps1.group_cap
    assert caps2.cand_cap >= caps1.cand_cap
    # a third call with the small batch must reuse the grown caps
    idx.predict(ss.query_batch(n=16), mode="kernel", stats=s1)
    assert not s1["caps_grew"]


# --------------------------------------------------------------------------
# insert
# --------------------------------------------------------------------------

@pytest.mark.parametrize("name", SERVING)
def test_insert_matches_from_scratch_recluster(name, fitted):
    """Acceptance: insert + read-out ≡ cluster() on the union set
    (canonicalized, contested borders excepted)."""
    ss, pts, res = fitted(name)
    snap = res.index.snapshot()
    idx = GritIndex.restore(snap)          # do not mutate the fixture
    batches = ss.insert_batches()
    for b in batches:
        st = idx.insert(b)
        assert st["inserted"] == len(b)
    union = np.concatenate([pts] + batches)
    assert idx.n == len(union)
    ref = brute_dbscan(union, ss.base.eps, ss.base.min_pts)
    assert_labels_conformant(union, ss.base.eps, ss.base.min_pts, ref,
                             idx.labels_arrival())
    # core flags must match the union oracle exactly
    np.testing.assert_array_equal(
        idx.core_arrival(),
        core_flags(union, ss.base.eps, ss.base.min_pts))


def test_insert_outside_bbox_shifts_identifier_origin(fitted):
    ss, pts, res = fitted("drift-2d")
    idx = GritIndex.restore(res.index.snapshot())
    below = pts.min(axis=0) - 10 * ss.base.eps
    batch = below[None, :] + np.random.default_rng(0).uniform(
        0, ss.base.eps, size=(8, idx.d))
    st = idx.insert(batch)
    assert st["id_shifted"]
    assert (idx.ids >= 0).all()
    assert (idx.id_shift > 0).any()
    # identifiers of OLD points must still resolve to their stored grid
    qids = idx.query_ids(idx.points)
    row_ids = np.repeat(idx.ids, idx.counts, axis=0)
    np.testing.assert_array_equal(qids, row_ids)


def test_insert_then_predict_uses_new_cores(fitted):
    """A dense inserted blob far from the fit set must turn its region
    from noise into a predictable cluster."""
    ss, pts, res = fitted("drift-2d")
    idx = GritIndex.restore(res.index.snapshot())
    rng = np.random.default_rng(7)
    center = pts.max(axis=0) + 50 * ss.base.eps
    blob = center + rng.normal(scale=0.3 * ss.base.eps,
                               size=(4 * ss.base.min_pts, idx.d))
    probe = center[None, :]
    assert idx.predict(probe)[0] == -1
    idx.insert(blob)
    lab = idx.predict(probe)[0]
    assert lab >= 0
    # and the new cluster id is one the fit never used
    assert lab >= res.n_clusters


@pytest.mark.parametrize("seed", range(4))
def test_insert_random_stress(seed):
    """Randomized splice property: blobs + uniform base, then batches
    engineered to bridge clusters (lerp between random base pairs),
    promote borders to core (jittered copies), and open new regions
    (uniform, partly outside the bounding box).  Union labels must stay
    conformant with the brute oracle after every batch."""
    rng = np.random.default_rng(1000 + seed)
    eps, min_pts = 6.0, 4
    centers = rng.uniform(20, 80, size=(3, 2))
    base = np.concatenate([
        centers[rng.integers(0, 3, 90)] + rng.normal(scale=4.0,
                                                     size=(90, 2)),
        rng.uniform(0, 100, size=(20, 2)),
    ])
    idx = cluster(base, eps, min_pts, engine="grit",
                  return_index=True).index
    inserted = []
    for _ in range(3):
        a, b = base[rng.integers(0, len(base), (2, 12))]
        bridge = a + rng.uniform(0, 1, size=(12, 1)) * (b - a)
        batch = np.concatenate([
            bridge,
            base[rng.integers(0, len(base), 8)] + rng.normal(
                scale=0.5 * eps, size=(8, 2)),
            rng.uniform(-15, 115, size=(8, 2)),
        ])
        idx.insert(batch)
        inserted.append(batch)
        union = np.concatenate([base] + inserted)
        ref = brute_dbscan(union, eps, min_pts)
        assert_labels_conformant(union, eps, min_pts, ref,
                                 idx.labels_arrival())


def test_insert_validates_inputs(fitted):
    _, _, res = fitted("drift-2d")
    idx = GritIndex.restore(res.index.snapshot())
    with pytest.raises(ValueError, match="insert batch"):
        idx.insert(np.zeros((3, 7)))
    bad = np.zeros((2, 2))
    bad[0, 1] = np.inf
    with pytest.raises(ValueError, match="non-finite"):
        idx.insert(bad)
    # an empty batch is a no-op but returns the full stats shape (a
    # serving loop may log st["newly_core"] etc. unconditionally)
    st = idx.insert(np.zeros((0, 2)))
    assert st["inserted"] == 0 and st["newly_core"] == 0
    assert "t_total" in st and "affected_grids" in st


def test_fit_grid_invariant_survives_id_shift(fitted):
    """fit_grid must keep the GridIndex contract ids == floor((x -
    mins)/side) even after an insert translated the stored lattice."""
    ss, pts, res = fitted("drift-2d")
    idx = GritIndex.restore(res.index.snapshot())
    idx.insert(pts.min(axis=0)[None, :] - 7 * ss.base.eps)
    assert (idx.id_shift > 0).any()
    gi = idx.fit_grid
    order_pts = idx.points[np.argsort(idx.arrival)]
    want = np.floor((order_pts - gi.mins[None, :]) / gi.side)
    np.testing.assert_array_equal(gi.ids[gi.point_grid],
                                  want.astype(np.int64))


# --------------------------------------------------------------------------
# snapshot / restore
# --------------------------------------------------------------------------

def test_snapshot_roundtrip_bitexact(fitted):
    ss, pts, res = fitted("query-heavy-3d")
    idx = res.index
    snap = idx.snapshot()
    assert all(isinstance(v, np.ndarray) for v in snap.values()), \
        "snapshot must be flat numpy arrays (savez-able)"
    buf = io.BytesIO()
    idx.save(buf)
    buf.seek(0)
    idx2 = GritIndex.load(buf)
    for f in ("points", "arrival", "ids", "starts", "counts", "core",
              "labels", "mins", "id_shift"):
        np.testing.assert_array_equal(getattr(idx, f), getattr(idx2, f))
    assert (idx2.eps, idx2.min_pts, idx2.side, idx2.next_label) == \
        (idx.eps, idx.min_pts, idx.side, idx.next_label)
    q = ss.query_batch()
    np.testing.assert_array_equal(idx.predict(q, mode="host"),
                                  idx2.predict(q, mode="host"))
    # a restored index must keep serving inserts
    idx2.insert(ss.insert_batches()[0])


def test_snapshot_version_checked(fitted):
    _, _, res = fitted("drift-2d")
    snap = res.index.snapshot()
    snap["version"] = np.asarray([99], np.int64)
    with pytest.raises(ValueError, match="snapshot version"):
        GritIndex.restore(snap)


def test_snapshot_preserves_device_caps():
    sc = scenario_map()["blobs-2d"]
    pts = sc.points()
    res = cluster(pts, sc.eps, sc.min_pts, engine="device",
                  return_index=True)
    idx = res.index
    assert idx.caps is not None, "device fit must carry its GritCaps"
    idx2 = GritIndex.restore(idx.snapshot())
    assert idx2.caps == idx.caps


# --------------------------------------------------------------------------
# return_index across engines + result provenance
# --------------------------------------------------------------------------

@pytest.mark.parametrize("engine", ["brute", "grit", "grit-ldf", "device"])
def test_return_index_for_every_engine(engine):
    sc = scenario_map()["blobs-2d"]
    pts = sc.points()
    res = cluster(pts, sc.eps, sc.min_pts, engine=engine,
                  return_index=True)
    idx = res.index
    assert isinstance(idx, GritIndex)
    np.testing.assert_array_equal(idx.labels_arrival(), res.labels)
    np.testing.assert_array_equal(idx.core_arrival(), res.core)
    # predicting a fitted core point returns its own cluster
    ci = int(np.flatnonzero(res.core)[0])
    assert idx.predict(pts[ci:ci + 1], mode="host")[0] == res.labels[ci]


def test_fit_index_helper():
    sc = scenario_map()["blobs-2d"]
    pts = sc.points()
    idx = fit_index(pts, sc.eps, sc.min_pts, engine="grit")
    assert isinstance(idx, GritIndex) and idx.n == len(pts)


def test_return_index_distributed_engine_carries_core():
    """The distributed engine now reports exact core flags (the SPMD
    step returns them per shard), so return_index must consume them
    directly instead of the grid-based fallback identification."""
    sc = scenario_map()["cross-slab-2d"]
    pts = sc.points()
    res = cluster(pts, sc.eps, sc.min_pts, engine="distributed",
                  return_index=True)
    assert res.core is not None, \
        "distributed result must carry core flags"
    np.testing.assert_array_equal(res.core,
                                  core_flags(pts, sc.eps, sc.min_pts))
    idx = res.index
    np.testing.assert_array_equal(idx.core_arrival(), res.core)
    ci = int(np.flatnonzero(res.core)[0])
    assert idx.predict(pts[ci:ci + 1], mode="host")[0] == res.labels[ci]


def test_cluster_result_carries_provenance():
    """Satellite: core indices + grid provenance ride on ClusterResult
    so downstream tooling does not re-derive them."""
    sc = scenario_map()["blobs-2d"]
    pts = sc.points()
    res = cluster(pts, sc.eps, sc.min_pts, engine="grit")
    np.testing.assert_array_equal(res.core_idx, np.flatnonzero(res.core))
    gi = res.grid
    assert isinstance(gi, GridIndex)
    ids, mins, side = identifiers(pts, sc.eps)
    np.testing.assert_array_equal(gi.ids[gi.point_grid], ids)
    assert gi.side == side
    # brute carries core_idx but no grid machinery
    res_b = cluster(pts, sc.eps, sc.min_pts, engine="brute")
    assert res_b.grid is None and res_b.core_idx is not None
