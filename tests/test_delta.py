"""Mutation-plane conformance: the delta engine's ``delete`` (and its
interleaving with ``insert``) must leave both index flavors
label-conformant with a from-scratch ``cluster()`` on the *surviving*
set after every op -- DBSCAN is not monotone under deletion, so this
pins the whole touched-stencil / merge-graph / component-relabel
machinery, including cluster splits, core demotions, deletes below the
shifted origin, emptied grids and threshold compaction.  Also covers
the persistent merge graph (incremental maintenance ==
built-from-scratch), the v1/v2 snapshot compatibility and the unified
mutation stats schema.
"""

import io

import numpy as np
import pytest

from repro.core.dbscan import brute_dbscan
from repro.core.validate import assert_labels_conformant, core_flags
from repro.data.scenarios import churn_scenarios, get_churn_scenario
from repro.engine import cluster
from repro.index import (GritIndex, ShardedGritIndex, build_merge_graph,
                         fit_sharded)
from repro.index.delta import grid_components

CHURN = sorted(s.name for s in churn_scenarios())


def _fit_index(pts, eps, min_pts):
    return cluster(pts, eps, min_pts, engine="grit",
                   return_index=True).index


def _replay(index, ops, base, eps, min_pts, check_every=True):
    """Apply a churn op stream, checking conformance vs the brute
    oracle on the surviving set after every op (or only at the end)."""
    live = {i: p for i, p in enumerate(base)}
    nid = len(base)
    for t, (kind, payload) in enumerate(ops):
        if kind == "insert":
            st = index.insert(payload)
            assert st["inserted"] == len(payload)
            for p in payload:
                live[nid] = p
                nid += 1
        else:
            st = index.delete(payload)
            assert st["deleted"] == sum(int(i) in live for i in payload)
            for i in payload:
                live.pop(int(i), None)
        surv = np.array([live[i] for i in sorted(live)])
        np.testing.assert_array_equal(
            np.fromiter(sorted(live), np.int64, len(live)),
            index.arrival_live())
        if check_every or t == len(ops) - 1:
            ref = brute_dbscan(surv, eps, min_pts)
            assert_labels_conformant(surv, eps, min_pts, ref,
                                     index.labels_arrival())
            np.testing.assert_array_equal(
                index.core_arrival(), core_flags(surv, eps, min_pts))
    return live


# --------------------------------------------------------------------------
# churn scenarios: single-host and host-sharded
# --------------------------------------------------------------------------

@pytest.mark.parametrize("name", CHURN)
def test_churn_scenario_conformant(name):
    """Acceptance: every churn op leaves the read-out ≡ cluster() on
    the surviving set (single-host index)."""
    cs = get_churn_scenario(name)
    pts = cs.fit_points()
    eps, min_pts = cs.base.eps, cs.base.min_pts
    _replay(_fit_index(pts, eps, min_pts), cs.ops(), pts, eps, min_pts)


@pytest.mark.parametrize("name", CHURN)
def test_churn_scenario_conformant_sharded(name):
    """The same op streams through a host-sharded ShardedGritIndex
    (owner + ghost-copy removal, label-map rebuild on splits)."""
    cs = get_churn_scenario(name)
    pts = cs.fit_points()
    eps, min_pts = cs.base.eps, cs.base.min_pts
    sidx = fit_sharded(pts, eps, min_pts, n_shards=4, engine="grit")
    _replay(sidx, cs.ops(), pts, eps, min_pts)


def test_bridge_cut_splits_cluster_in_two():
    """Deleting a bridge must split the merged cluster back into two
    components -- the acceptance scenario for non-monotone deletion."""
    rng = np.random.default_rng(3)
    eps, min_pts = 5.0, 4
    left = np.array([20.0, 50.0]) + rng.normal(scale=1.5,
                                               size=(6 * min_pts, 2))
    right = np.array([80.0, 50.0]) + rng.normal(scale=1.5,
                                                size=(6 * min_pts, 2))
    base = np.concatenate([left, right])
    idx = _fit_index(base, eps, min_pts)
    assert len(set(idx.labels_arrival().tolist()) - {-1}) == 2
    t = np.linspace(0, 1, 60)[:, None]
    bridge = left[0] + t * (right[0] - left[0]) + rng.normal(
        scale=0.2, size=(60, 2))
    idx.insert(bridge)
    la = idx.labels_arrival()
    assert len(set(la[la >= 0].tolist())) == 1, "bridge must merge"
    st = idx.delete(np.arange(len(base), len(base) + 60))
    assert st["deleted"] == 60
    la = idx.labels_arrival()
    assert len(set(la[la >= 0].tolist())) == 2, "cut must split"
    ref = brute_dbscan(base, eps, min_pts)
    assert_labels_conformant(base, eps, min_pts, ref, la)


def test_delete_demotes_core_to_border_and_noise():
    """Thinning a neighborhood below MinPts must demote its cores, and
    the demoted rows must re-take the border test themselves."""
    rng = np.random.default_rng(5)
    eps, min_pts = 4.0, 6
    blob = np.full(2, 50.0) + rng.normal(scale=1.0, size=(40, 2))
    idx = _fit_index(blob, eps, min_pts)
    assert idx.core_arrival().all()
    keep_n = min_pts - 2
    kill = np.arange(keep_n, 40)
    st = idx.delete(kill)
    surv = blob[:keep_n]
    np.testing.assert_array_equal(idx.core_arrival(),
                                  core_flags(surv, eps, min_pts))
    ref = brute_dbscan(surv, eps, min_pts)
    assert_labels_conformant(surv, eps, min_pts, ref,
                             idx.labels_arrival())
    assert st["demoted"] > 0


def test_delete_below_origin_after_id_shift():
    """Insert below the fitted origin (lattice translation), then
    delete those same points: identifiers must keep resolving through
    the shifted lattice on both mutations."""
    rng = np.random.default_rng(7)
    eps, min_pts = 5.0, 4
    base = rng.uniform(40, 90, size=(120, 2))
    idx = _fit_index(base, eps, min_pts)
    below = base.min(axis=0) - 9 * eps + rng.uniform(
        0, 2 * eps, size=(4 * min_pts, 2))
    st = idx.insert(below)
    assert st["id_shifted"] and (idx.id_shift > 0).any()
    ids = np.arange(len(base), len(base) + len(below))
    st = idx.delete(ids[::2])
    surv = np.concatenate([base, below[1::2]])
    ref = brute_dbscan(surv, eps, min_pts)
    assert_labels_conformant(surv, eps, min_pts, ref,
                             idx.labels_arrival())
    st = idx.delete(ids[1::2])
    assert st["deleted"] == len(ids[1::2])
    ref = brute_dbscan(base, eps, min_pts)
    assert_labels_conformant(base, eps, min_pts, ref,
                             idx.labels_arrival())
    # old points still resolve to their stored (shifted) grids
    qids = idx.query_ids(idx.points[idx.alive])
    row_ids = np.repeat(idx.ids, idx.counts, axis=0)[idx.alive]
    np.testing.assert_array_equal(qids, row_ids)


def test_delete_everything_in_a_grid():
    """Emptying one grid outright (its rows all dead) must survive both
    the tombstone phase and the compaction that drops the grid."""
    rng = np.random.default_rng(9)
    eps, min_pts = 6.0, 4
    base = rng.uniform(0, 100, size=(150, 2))
    idx = _fit_index(base, eps, min_pts)
    g = int(np.argmax(idx.live_counts))
    rows = np.arange(idx.starts[g], idx.starts[g] + idx.counts[g])
    ids = idx.arrival[rows]
    grids_before = idx.num_grids
    st = idx.delete(ids)
    assert st["deleted"] == len(ids)
    surv = np.delete(base, ids, axis=0)
    ref = brute_dbscan(surv, eps, min_pts)
    assert_labels_conformant(surv, eps, min_pts, ref,
                             idx.labels_arrival())
    idx.compact()
    assert idx.num_grids < grids_before
    assert idx.n == idx.n_live == len(surv)
    assert_labels_conformant(surv, eps, min_pts, ref,
                             idx.labels_arrival())


def test_delete_everything_then_reuse():
    rng = np.random.default_rng(11)
    eps, min_pts = 5.0, 4
    base = rng.uniform(0, 60, size=(80, 2))
    idx = _fit_index(base, eps, min_pts)
    idx.delete(np.arange(80))
    assert idx.n_live == 0
    assert (idx.predict(base[:7]) == -1).all()
    # and the empty index accepts fresh inserts
    blob = np.full(2, 30.0) + rng.normal(scale=0.8,
                                         size=(4 * min_pts, 2))
    idx.insert(blob)
    ref = brute_dbscan(blob, eps, min_pts)
    assert_labels_conformant(blob, eps, min_pts, ref,
                             idx.labels_arrival())


def test_delete_rejects_unknown_and_double_deletes():
    rng = np.random.default_rng(13)
    base = rng.uniform(0, 50, size=(60, 2))
    idx = _fit_index(base, 4.0, 4)
    st = idx.delete([3, 4, 10 ** 7, -5])
    assert st["deleted"] == 2 and st["rejected"] == 2
    assert set(st["rejected_ids"].tolist()) == {10 ** 7, -5}
    st = idx.delete([3, 4])                  # double delete: rejected
    assert st["deleted"] == 0 and st["rejected"] == 2
    st = idx.delete(np.zeros(0, np.int64))   # empty: full stats shape
    assert st["deleted"] == 0 and "t_total" in st \
        and "affected_grids" in st


@pytest.mark.parametrize("seed", range(4))
def test_churn_random_stress(seed):
    """Randomized insert/delete interleaving: bridges, jittered copies,
    fresh regions, then deletions of a random fifth of the live set --
    conformant vs the brute oracle after every step."""
    rng = np.random.default_rng(2000 + seed)
    eps, min_pts = 6.0, 4
    centers = rng.uniform(20, 80, size=(3, 2))
    base = np.concatenate([
        centers[rng.integers(0, 3, 90)] + rng.normal(scale=4.0,
                                                     size=(90, 2)),
        rng.uniform(0, 100, size=(20, 2)),
    ])
    idx = _fit_index(base, eps, min_pts)
    live = {i: p for i, p in enumerate(base)}
    nid = len(base)
    for _ in range(3):
        a, b = base[rng.integers(0, len(base), (2, 12))]
        batch = np.concatenate([
            a + rng.uniform(0, 1, size=(12, 1)) * (b - a),
            base[rng.integers(0, len(base), 8)] + rng.normal(
                scale=0.5 * eps, size=(8, 2)),
            rng.uniform(-15, 115, size=(8, 2)),
        ])
        idx.insert(batch)
        for p in batch:
            live[nid] = p
            nid += 1
        kill = rng.choice(sorted(live), size=len(live) // 5,
                          replace=False)
        idx.delete(kill)
        for k in kill:
            live.pop(int(k))
        surv = np.array([live[i] for i in sorted(live)])
        ref = brute_dbscan(surv, eps, min_pts)
        assert_labels_conformant(surv, eps, min_pts, ref,
                                 idx.labels_arrival())
        np.testing.assert_array_equal(
            idx.core_arrival(), core_flags(surv, eps, min_pts))


# --------------------------------------------------------------------------
# persistent merge graph
# --------------------------------------------------------------------------

def test_merge_graph_incremental_equals_from_scratch():
    """After arbitrary churn, the incrementally-maintained edge array
    must equal a from-scratch FastMerging decision over the same
    state -- the invariant everything above stands on."""
    cs = get_churn_scenario("churn-split-2d")
    pts = cs.fit_points()
    eps, min_pts = cs.base.eps, cs.base.min_pts
    idx = _fit_index(pts, eps, min_pts)
    for kind, payload in cs.ops():
        (idx.insert if kind == "insert" else idx.delete)(payload)
        fresh = GritIndex.restore(idx.snapshot())
        fresh.merge_edges = None
        np.testing.assert_array_equal(idx.merge_edges,
                                      build_merge_graph(fresh))


def test_merge_graph_bbox_covers_last_core_grid():
    """Regression: the batch edge evaluator's bbox tier must cover the
    *entire* last core-bearing grid even when zero-core grids sort
    after it (a clamped reduceat boundary used to shear that grid's
    final core row out of its bbox, falsely rejecting a true edge --
    and a later unrelated delete then split the cluster)."""
    eps, min_pts = 1.0, 3
    base = np.array([[0.04, 0.0], [0.05, 0.0], [0.06, 0.0],
                     [1.04, 0.0], [1.41, 0.0], [1.41, 0.01],
                     [5.0, 0.0]])              # lone noise, lex-last grid
    idx = _fit_index(base, eps, min_pts)
    edges = idx.ensure_merge_graph()
    assert len(edges) == 1, "the A-B core-grid edge must be found"
    st = idx.delete([6])                      # unrelated noise point
    assert st["deleted"] == 1
    la = idx.labels_arrival()
    assert len(set(la[la >= 0].tolist())) == 1, \
        "deleting unrelated noise must not split the cluster"
    ref = brute_dbscan(base[:6], eps, min_pts)
    assert_labels_conformant(base[:6], eps, min_pts, ref, la)


def test_grid_components_matches_bfs():
    rng = np.random.default_rng(17)
    G = 40
    edges = np.unique(np.sort(rng.integers(0, G, size=(60, 2)), axis=1),
                      axis=0)
    edges = edges[edges[:, 0] != edges[:, 1]]
    comp = grid_components(G, edges)
    # brute reference: repeated relaxation
    ref = np.arange(G)
    for _ in range(G):
        for a, b in edges:
            m = min(ref[a], ref[b])
            ref[a] = ref[b] = m
    for _ in range(G):
        ref = ref[ref]
    np.testing.assert_array_equal(comp, ref)


def test_compaction_threshold_triggers_and_preserves_state():
    rng = np.random.default_rng(19)
    eps, min_pts = 5.0, 4
    base = rng.uniform(0, 80, size=(200, 2))
    idx = _fit_index(base, eps, min_pts)
    idx.compact_threshold = 0.1
    st = idx.delete(np.arange(0, 60))        # 30% dead > 10% threshold
    assert st["compacted"] and idx.n == idx.n_live == 140
    surv = base[60:]
    ref = brute_dbscan(surv, eps, min_pts)
    assert_labels_conformant(surv, eps, min_pts, ref,
                             idx.labels_arrival())
    # predict against the compacted state == predict on a fresh fit
    q = surv[:20] + rng.normal(scale=0.2 * eps, size=(20, 2))
    fresh = _fit_index(surv, eps, min_pts)
    got, ref_lab = idx.predict(q, mode="host"), fresh.predict(q,
                                                             mode="host")
    np.testing.assert_array_equal(got == -1, ref_lab == -1)


# --------------------------------------------------------------------------
# snapshots: v2 round-trip + v1 back-compat
# --------------------------------------------------------------------------

def _strip_to_v1(snap):
    """Rewrite a v2 GritIndex snapshot as its v1 schema."""
    v1 = {k: v for k, v in snap.items()
          if k not in ("alive", "live_counts", "merge_edges",
                       "has_merge_graph")}
    v1["version"] = np.asarray([1], np.int64)
    v1["scalars_i"] = snap["scalars_i"][:2]
    return v1


def test_snapshot_v2_roundtrip_after_churn():
    cs = get_churn_scenario("ttl-drift-3d")
    pts = cs.fit_points()
    eps, min_pts = cs.base.eps, cs.base.min_pts
    idx = _fit_index(pts, eps, min_pts)
    live = _replay(idx, cs.ops(), pts, eps, min_pts, check_every=False)
    buf = io.BytesIO()
    idx.save(buf)
    buf.seek(0)
    idx2 = GritIndex.load(buf)
    for f in ("points", "arrival", "ids", "starts", "counts", "core",
              "labels", "alive", "live_counts", "merge_edges"):
        np.testing.assert_array_equal(getattr(idx, f), getattr(idx2, f))
    assert idx2.next_arrival == idx.next_arrival
    np.testing.assert_array_equal(idx.labels_arrival(),
                                  idx2.labels_arrival())
    # the restored index keeps mutating exactly
    ids = idx2.arrival_live()[:10]
    idx2.delete(ids)
    surv = np.array([live[i] for i in sorted(live)
                     if i not in set(ids.tolist())])
    ref = brute_dbscan(surv, eps, min_pts)
    assert_labels_conformant(surv, eps, min_pts, ref,
                             idx2.labels_arrival())


def test_snapshot_v1_still_restores_and_mutates():
    """A previous-version snapshot (no tombstones, no merge graph)
    must restore, rebuild the merge graph lazily on the first
    mutation, and serve deletes exactly."""
    rng = np.random.default_rng(23)
    eps, min_pts = 5.0, 4
    base = rng.uniform(0, 70, size=(150, 2))
    idx = _fit_index(base, eps, min_pts)
    v1 = _strip_to_v1(idx.snapshot())
    idx2 = GritIndex.restore(v1)
    assert idx2.merge_edges is None and idx2.alive.all()
    assert idx2.next_arrival == len(base)
    np.testing.assert_array_equal(idx2.labels_arrival(),
                                  idx.labels_arrival())
    st = idx2.delete(np.arange(0, 30))
    assert st["merge_graph_built"]
    surv = base[30:]
    ref = brute_dbscan(surv, eps, min_pts)
    assert_labels_conformant(surv, eps, min_pts, ref,
                             idx2.labels_arrival())


def test_snapshot_unknown_version_rejected():
    rng = np.random.default_rng(29)
    idx = _fit_index(rng.uniform(0, 50, size=(60, 2)), 4.0, 4)
    snap = idx.snapshot()
    snap["version"] = np.asarray([99], np.int64)
    with pytest.raises(ValueError, match="snapshot version"):
        GritIndex.restore(snap)


def test_sharded_snapshot_roundtrip_after_delete():
    cs = get_churn_scenario("churn-split-2d")
    pts = cs.fit_points()
    eps, min_pts = cs.base.eps, cs.base.min_pts
    sidx = fit_sharded(pts, eps, min_pts, n_shards=3, engine="grit")
    live = _replay(sidx, cs.ops()[:4], pts, eps, min_pts,
                   check_every=False)
    assert sidx.localized
    buf = io.BytesIO()
    sidx.save(buf)
    buf.seek(0)
    s2 = ShardedGritIndex.load(buf)
    assert s2.localized
    np.testing.assert_array_equal(s2.labels_arrival(),
                                  sidx.labels_arrival())
    ids = s2.arrival_live()[-8:]
    s2.delete(ids)
    surv = np.array([live[i] for i in sorted(live)
                     if i not in set(ids.tolist())])
    ref = brute_dbscan(surv, eps, min_pts)
    assert_labels_conformant(surv, eps, min_pts, ref,
                             s2.labels_arrival())


# --------------------------------------------------------------------------
# unified stats schema + compat shim
# --------------------------------------------------------------------------

_SHARED_INSERT = {"op", "inserted", "n", "n_live", "touched_grids",
                  "affected_grids", "changed_grids", "newly_core",
                  "merge_checks", "dist_evals", "relabeled",
                  "id_shifted", "t_total"}
_SHARED_DELETE = {"op", "requested", "deleted", "rejected",
                  "rejected_ids", "n", "n_live", "touched_grids",
                  "affected_grids", "changed_grids", "demoted",
                  "merge_checks", "dist_evals", "relabeled",
                  "compacted", "t_total"}


def test_unified_mutation_stats_schema():
    """GritIndex and ShardedGritIndex mutations share one stats schema
    (sharded sums the counters), so the serve driver can consume
    either without special-casing."""
    rng = np.random.default_rng(31)
    base = rng.uniform(0, 100, size=(160, 2))
    eps, min_pts = 6.0, 4
    idx = _fit_index(base, eps, min_pts)
    sidx = fit_sharded(base, eps, min_pts, n_shards=3, engine="grit")
    batch = rng.uniform(0, 100, size=(20, 2))
    s1, s2 = idx.insert(batch), sidx.insert(batch)
    assert _SHARED_INSERT <= set(s1) and _SHARED_INSERT <= set(s2)
    for f in ("inserted", "n", "n_live"):
        assert s1[f] == s2[f], f
    d1, d2 = idx.delete(np.arange(10)), sidx.delete(np.arange(10))
    assert _SHARED_DELETE <= set(d1) and _SHARED_DELETE <= set(d2)
    assert d1["deleted"] == d2["deleted"] == 10
    assert d1["demoted"] == d2["demoted"]
    # empty batches return the full schema (serving loops log
    # unconditionally)
    assert _SHARED_INSERT <= set(idx.insert(np.zeros((0, 2))))
    assert _SHARED_INSERT <= set(sidx.insert(np.zeros((0, 2))))


def test_insert_batch_compat_shim():
    """`insert_batch` stays importable from its pre-refactor home, now
    behind a DeprecationWarning pointing at the unified mutation
    plane."""
    import importlib
    import sys

    sys.modules.pop("repro.index.insert", None)
    with pytest.warns(DeprecationWarning, match=r"repro\.index\.delta"):
        shim_mod = importlib.import_module("repro.index.insert")
    shim = shim_mod.insert_batch
    from repro.index.delta import insert_batch as real
    assert shim is real
    assert shim_mod.__all__ == ["insert_batch"]
    rng = np.random.default_rng(37)
    idx = _fit_index(rng.uniform(0, 40, size=(50, 2)), 4.0, 4)
    st = shim(idx, rng.uniform(0, 40, size=(5, 2)))
    assert st["inserted"] == 5
