"""Topology-change exactness: split/merge must be pure re-partitions.

The contract (DESIGN.md §7): ``split_shard`` / ``merge_shards`` change
*where* points live, never *what* the index answers.  In the
insert-only regime the read-outs are bit-identical to a
never-rebalanced index (labels keep their minted ids -- the witness
-edge rebuild preserves identity even for clusters straddling the new
cut); in the localized regime (after any delete) ids may re-mint but
the partition and the core flags stay exact.  Replicas replay the
primary's mutation log -- topology ops included -- and serve
bit-identically; the rebalancer only ever applies these two ops, so
its policy layer is tested here too.
"""

import numpy as np
import pytest

from repro.dist.rebalance import RebalancePolicy, Rebalancer
from repro.index import ReplicaIndex, fit_index, fit_sharded, make_replicas

EPS, MIN_PTS = 0.6, 6


def canon(labels):
    """Canonical partition form: label -> first-occurrence rank."""
    out = np.full(len(labels), -1, np.int64)
    m = {}
    for i, v in enumerate(labels):
        if v >= 0:
            out[i] = m.setdefault(int(v), len(m))
    return out


@pytest.fixture()
def blobs():
    rng = np.random.default_rng(7)
    return np.concatenate([
        rng.normal((0, 0), 1.0, (400, 2)),
        rng.normal((8, 1), 1.2, (400, 2)),
        rng.normal((4, -3), 0.8, (300, 2)),
    ])


@pytest.fixture()
def pair(blobs):
    """(mutated, reference) -- two identical sharded fits; topology ops
    are applied to the first, the second never rebalances."""
    return (fit_sharded(blobs, EPS, MIN_PTS, n_shards=3),
            fit_sharded(blobs, EPS, MIN_PTS, n_shards=3))


class TestSplitMergeExactness:
    def test_split_is_bit_identical(self, pair):
        sidx, ref = pair
        st = sidx.split_shard(1)
        assert st["num_shards"] == 4
        assert st["n_left"] > 0 and st["n_right"] > 0
        assert np.array_equal(sidx.labels_arrival(), ref.labels_arrival())
        assert np.array_equal(sidx.core_arrival(), ref.core_arrival())

    def test_merge_is_bit_identical(self, pair):
        sidx, ref = pair
        st = sidx.merge_shards(0)
        assert st["num_shards"] == 2
        assert np.array_equal(sidx.labels_arrival(), ref.labels_arrival())
        assert np.array_equal(sidx.core_arrival(), ref.core_arrival())

    def test_split_merge_round_trip_restores_topology(self, pair):
        sidx, ref = pair
        cuts0 = sidx.cuts.copy()
        st = sidx.split_shard(1)
        assert len(sidx.cuts) == len(cuts0) + 1
        st2 = sidx.merge_shards(1)
        assert st2["cut"] == st["cut"]
        assert np.array_equal(sidx.cuts, cuts0)
        assert np.array_equal(sidx.labels_arrival(), ref.labels_arrival())
        assert np.array_equal(sidx.core_arrival(), ref.core_arrival())
        assert [op for op, _, _ in sidx.cut_history] == ["split", "merge"]

    def test_split_straddling_cross_cut_cluster(self):
        """A dense strip crossing the new cut: the split separates one
        cluster's members into both sub-shards, and the witness-edge
        rebuild must stitch them back to ONE label, bit-identical to
        the never-split labels."""
        rng = np.random.default_rng(3)
        strip = np.column_stack([rng.uniform(0.0, 10.0, 2000),
                                 rng.normal(0.0, 0.3, 2000)])
        sidx = fit_sharded(strip, EPS, MIN_PTS, n_shards=2)
        ref = fit_sharded(strip, EPS, MIN_PTS, n_shards=2)
        # one connected cluster spanning both slabs
        labs = ref.labels_arrival()
        assert len(np.unique(labs[labs >= 0])) == 1
        st = sidx.split_shard(0)
        # the new cut lands inside the strip -> the cluster straddles it
        assert 0.0 < st["cut"] < 10.0
        assert np.array_equal(sidx.labels_arrival(), labs)
        assert np.array_equal(sidx.core_arrival(), ref.core_arrival())
        st2 = sidx.split_shard(2)
        assert 0.0 < st2["cut"] < 10.0
        assert np.array_equal(sidx.labels_arrival(), labs)

    def test_insert_into_locally_disconnected_cluster(self):
        """A U-shaped cluster whose arms connect only through a bridge
        OUTSIDE a slab's pooled view: inside that shard one global
        cluster id spans two *local* components.  The first insert
        re-runs component labeling there and must not write the
        split-cluster sentinel (-2) into border rows -- they take the
        from-scratch border test and stay bit-identical to the
        never-sharded reference (both straight after the sharded fit
        and after a further split)."""
        xs = np.arange(0.0, 10.05, 0.1)
        ys = np.arange(0.2, 5.85, 0.1)
        u = np.concatenate([
            np.column_stack([xs, np.zeros_like(xs)]),       # bottom arm
            np.column_stack([xs, np.full_like(xs, 6.0)]),   # top arm
            np.column_stack([np.full_like(ys, 10.0), ys]),  # bridge
            [[2.05, -0.59], [5.05, -0.59], [8.05, -0.59]],  # borders
        ])
        single = fit_index(u, EPS, MIN_PTS, engine="grit")
        labs = single.labels_arrival()
        assert len(np.unique(labs[labs >= 0])) == 1  # one U cluster
        assert (~single.core_arrival()[-3:]).all()   # borders non-core
        for pre_split in (False, True):
            ref = fit_index(u, EPS, MIN_PTS, engine="grit")
            sidx = fit_sharded(u, EPS, MIN_PTS, n_shards=2)
            # the bridge is beyond shard 0's ghost band: its pooled
            # view holds the one cluster as two local components
            assert sidx.cuts[0] < 10.0 - 2 * EPS
            if pre_split:
                sidx.split_shard(0)
            batch = np.asarray([[1.0, 0.05], [3.0, 5.95]])
            ref.insert(batch)
            sidx.insert(batch)
            out = sidx.labels_arrival()
            assert out.min() >= -1   # no -2 sentinel leaked
            assert np.array_equal(out, ref.labels_arrival())
            assert np.array_equal(sidx.core_arrival(),
                                  ref.core_arrival())

    def test_predict_stream_identical_after_ops(self, pair, blobs):
        sidx, ref = pair
        rng = np.random.default_rng(11)
        q = rng.normal((4, -1), 3.0, (300, 2))
        sidx.split_shard(1)
        assert np.array_equal(sidx.predict(q), ref.predict(q))
        sidx.merge_shards(1)
        assert np.array_equal(sidx.predict(q), ref.predict(q))

    def test_ops_compose_with_inserts(self, pair):
        """insert -> split -> insert -> merge stays identical to the
        same inserts on a static topology."""
        sidx, ref = pair
        rng = np.random.default_rng(5)
        b1 = rng.normal((8, 1), 1.2, (60, 2))
        b2 = rng.normal((0, 0), 1.0, (60, 2))
        sidx.insert(b1); ref.insert(b1)
        sidx.split_shard(2)
        sidx.insert(b2); ref.insert(b2)
        sidx.merge_shards(2)
        assert np.array_equal(sidx.labels_arrival(), ref.labels_arrival())
        assert np.array_equal(sidx.core_arrival(), ref.core_arrival())

    def test_localized_regime_partition_exact(self, pair):
        """After a delete (localized shards) topology ops re-mint ids;
        the partition and core flags must stay exact."""
        sidx, ref = pair
        dead = np.arange(0, 80, dtype=np.int64)
        sidx.delete(dead); ref.delete(dead)
        assert sidx.localized
        sidx.split_shard(1)
        sidx.merge_shards(1)
        assert np.array_equal(canon(sidx.labels_arrival()),
                              canon(ref.labels_arrival()))
        assert np.array_equal(sidx.core_arrival(), ref.core_arrival())

    def test_snapshot_split_merge_restore_round_trip(self, pair):
        """The satellite round-trip: snapshot -> split -> merge ->
        restore, read-outs bit-identical to never-rebalanced."""
        import repro.index.sharded as sh
        sidx, ref = pair
        snap = sidx.snapshot()
        back = sh.ShardedGritIndex.restore(snap)
        back.split_shard(1)
        back.merge_shards(1)
        snap2 = back.snapshot()
        final = sh.ShardedGritIndex.restore(snap2)
        assert np.array_equal(final.labels_arrival(),
                              ref.labels_arrival())
        assert np.array_equal(final.core_arrival(), ref.core_arrival())
        assert final.cut_history == back.cut_history


class TestTopologyValidation:
    def test_split_out_of_range(self, pair):
        with pytest.raises(ValueError):
            pair[0].split_shard(7)

    def test_merge_needs_adjacent(self, pair):
        sidx, _ = pair
        with pytest.raises(ValueError):
            sidx.merge_shards(0, 2)
        with pytest.raises(ValueError):
            sidx.merge_shards(2)      # k+1 out of range

    def test_unsplittable_single_column(self):
        rng = np.random.default_rng(0)
        pts = np.column_stack([5.0 + 0.1 * rng.random(60),
                               rng.normal(0, 3.0, 60)])
        sidx = fit_sharded(pts, 1.0, 3, n_shards=2)
        with pytest.raises(ValueError, match="unsplittable|no interior"):
            sidx.split_shard(0)


class TestReplica:
    def test_requires_mutation_log(self, blobs):
        idx = fit_index(blobs, EPS, MIN_PTS)
        with pytest.raises(ValueError, match="enable_mutation_log"):
            ReplicaIndex(idx)

    def test_replay_is_bit_identical(self, blobs):
        rng = np.random.default_rng(2)
        idx = fit_index(blobs[:900], EPS, MIN_PTS)
        idx.enable_mutation_log()
        rep = ReplicaIndex(idx)
        idx.insert(blobs[900:1000])
        idx.insert(blobs[1000:])
        idx.delete(np.arange(30, dtype=np.int64))
        assert rep.lag == 3
        assert rep.catch_up() == 3
        assert rep.lag == 0
        assert np.array_equal(rep.labels_arrival(), idx.labels_arrival())
        assert np.array_equal(rep.core_arrival(), idx.core_arrival())
        q = rng.normal((4, -1), 3.0, (200, 2))
        assert np.array_equal(rep.predict(q), idx.predict(q))

    def test_sharded_replica_replays_topology(self, blobs):
        rng = np.random.default_rng(4)
        sp = fit_sharded(blobs, EPS, MIN_PTS, n_shards=3)
        reps = make_replicas(sp, 2)
        sp.insert(rng.normal((8, 1), 1.2, (50, 2)))
        sp.split_shard(0)
        sp.insert(rng.normal((0, 0), 1.0, (50, 2)))
        sp.merge_shards(0)
        q = rng.normal((4, -1), 3.0, (200, 2))
        want = sp.predict(q)
        for rep in reps:
            assert np.array_equal(rep.predict(q), want)     # catches up
            assert np.array_equal(rep.labels_arrival(),
                                  sp.labels_arrival())
            assert rep.index.cut_history == sp.cut_history
            assert rep.lag == 0

    def test_read_only(self, blobs):
        idx = fit_index(blobs, EPS, MIN_PTS)
        idx.enable_mutation_log()
        rep = ReplicaIndex(idx)
        with pytest.raises(TypeError, match="read-only"):
            rep.insert(blobs[:2])
        with pytest.raises(TypeError, match="read-only"):
            rep.delete(np.asarray([0]))

    def test_stale_cursor_rejected(self, blobs):
        idx = fit_index(blobs, EPS, MIN_PTS)
        log = idx.enable_mutation_log()
        rep = ReplicaIndex(idx)
        idx.insert(blobs[:10] + 100.0)
        log.truncate(log.end)           # primary drops replayed history
        rep.cursor = 0
        with pytest.raises(ValueError, match="re-clone"):
            rep.catch_up()

    def test_log_truncate_keeps_live_suffix(self, blobs):
        idx = fit_index(blobs, EPS, MIN_PTS)
        log = idx.enable_mutation_log()
        rep = ReplicaIndex(idx)
        idx.insert(blobs[:10] + 100.0)
        idx.insert(blobs[10:20] + 100.0)
        rep.catch_up()
        idx.insert(blobs[20:30] + 100.0)
        assert log.truncate(rep.cursor) == 2
        assert rep.catch_up() == 1      # suffix still replayable
        assert np.array_equal(rep.labels_arrival(), idx.labels_arrival())


class TestRebalancer:
    def test_splits_hottest_after_period(self, blobs):
        sidx = fit_sharded(blobs, EPS, MIN_PTS, n_shards=3)
        rb = Rebalancer(RebalancePolicy(period=2, hot_factor=2.0))
        loads = [100.0, 10.0, 10.0]
        rb.observe(loads)
        assert rb.maybe_rebalance(sidx) is None   # inside the period
        rb.observe(loads)
        st = rb.maybe_rebalance(sidx)
        assert st is not None and st["op"] == "split" and st["shard"] == 0
        assert sidx.num_shards == 4
        assert rb.history == [st]
        assert rb.load is None                    # re-learns post-op

    def test_merges_coldest_adjacent_pair(self, blobs):
        sidx = fit_sharded(blobs, EPS, MIN_PTS, n_shards=3)
        rb = Rebalancer(RebalancePolicy(period=1, hot_factor=100.0,
                                        cold_factor=0.5))
        rb.observe([100.0, 1.0, 2.0])
        rb.steps = rb.policy.period + 1
        st = rb.maybe_rebalance(sidx)
        assert st is not None and st["op"] == "merge" and st["shard"] == 1
        assert sidx.num_shards == 2

    def test_no_op_when_balanced(self, blobs):
        sidx = fit_sharded(blobs, EPS, MIN_PTS, n_shards=3)
        rb = Rebalancer(RebalancePolicy(period=1))
        for _ in range(4):
            rb.observe([10.0, 11.0, 9.0])
        assert rb.maybe_rebalance(sidx) is None
        assert sidx.num_shards == 3

    def test_respects_max_shards(self, blobs):
        sidx = fit_sharded(blobs, EPS, MIN_PTS, n_shards=3)
        rb = Rebalancer(RebalancePolicy(period=1, max_shards=3))
        for _ in range(3):
            rb.observe([100.0, 1.0, 1.0])
        assert rb.maybe_rebalance(sidx) is None or \
            rb.history[0]["op"] != "split"
        assert sidx.num_shards <= 3

    def test_shard_count_change_resets_ewma(self):
        rb = Rebalancer()
        rb.observe([1.0, 2.0, 3.0])
        rb.observe([10.0, 20.0])      # topology changed under us
        assert np.array_equal(rb.load, [10.0, 20.0])

    def test_imbalance_gauge_math(self):
        rb = Rebalancer()
        rb.observe([30.0, 10.0, 20.0])
        assert rb.imbalance() == pytest.approx(30.0 / 20.0)

    def test_unsplittable_falls_through(self):
        # shard 0: one dim-0 grid column (unsplittable); shard 1: spread
        rng = np.random.default_rng(0)
        pts = np.column_stack([
            np.concatenate([5.0 + 0.1 * rng.random(60),
                            rng.uniform(20.0, 30.0, 60)]),
            rng.normal(0, 3.0, 120)])
        sidx = fit_sharded(pts, 1.0, 3, n_shards=2)
        assert sidx.num_shards == 2
        # hot_factor low enough to beat the 2-shard median (which the
        # hot shard itself drags up to 50.5)
        rb = Rebalancer(RebalancePolicy(period=1, hot_factor=1.5,
                                        cold_factor=0.0))
        for _ in range(3):
            rb.observe([100.0, 1.0])
        assert rb.maybe_rebalance(sidx) is None   # split raises, no merge
        assert 0 in rb._unsplittable


class TestServeIntegration:
    def _serve(self, blobs, **kw):
        from repro.serve.driver import ClusterServer
        sidx = fit_sharded(blobs, EPS, MIN_PTS, n_shards=3)
        srv = ClusterServer(sidx, slots=2, **kw)
        rng = np.random.default_rng(9)
        for i in range(12):
            if i % 4 == 3:
                srv.submit_insert(rng.normal((8, 1), 1.2, (20, 2)))
            else:
                srv.submit(rng.normal((4, -1), 3.0, (30, 2)))
        return srv, srv.run()

    def test_slab_gauges_exported(self, blobs):
        srv, _ = self._serve(blobs)
        snap = srv.metrics.snapshot()
        gauges = snap.get("gauges", snap)
        names = str(list(gauges))
        assert "serve.slab.imbalance" in names
        assert "serve.slab.load.0" in names

    @staticmethod
    def _assert_same_answers(a, b):
        (srv_a, done_a), (srv_b, done_b) = a, b
        assert len(done_a) == len(done_b)
        for ra, rb in zip(done_a, done_b):
            assert ra.kind == rb.kind
            if ra.kind == "predict":
                assert np.array_equal(ra.labels, rb.labels)
        assert np.array_equal(srv_a.index.labels_arrival(),
                              srv_b.index.labels_arrival())

    def test_rebalance_plane_applies_ops(self, blobs):
        """Load-triggered splits under a serving stream answer every
        request as a never-rebalanced topology does."""
        plain = self._serve(blobs)
        srv, done = self._serve(
            blobs, rebalance=RebalancePolicy(period=1, hot_factor=1.01,
                                             cold_factor=0.0))
        # aggressively low threshold -> at least one split happened
        assert srv.topology_events
        assert srv.index.num_shards > 3
        assert all(e["op"] == "split" for e in srv.topology_events)
        self._assert_same_answers(plain, (srv, done))

    def test_rebalance_needs_topology_backend(self, blobs):
        from repro.serve.driver import ClusterServer
        idx = fit_index(blobs, EPS, MIN_PTS)
        with pytest.raises(ValueError, match="split_shard"):
            ClusterServer(idx, rebalance=True)

    def test_replicated_reads_match_primary_serving(self, blobs):
        """Same request stream through a replicated server and a
        plain one: identical labels on every request."""
        plain = self._serve(blobs)
        srv_b, done_b = self._serve(blobs, replicas=2)
        assert len(srv_b.replicas) == 2
        assert srv_b._rr > 0            # reads actually fanned out
        self._assert_same_answers(plain, (srv_b, done_b))
