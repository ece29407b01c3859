"""Differential device-vs-host test plane for the serving hot path.

The device-resident path (``GritIndex.ensure_device_state``) must be
**bit-identical** to host serving -- not approximately: the guard-band
discipline (``repro.index.device_state``) only lets the float32 kernels
decide provably-certain cases and re-runs the uncertain band through
the same host float64 code, so every observable output -- predict
labels *and* squared distances, ``labels_arrival`` / ``core_arrival``,
the merge-edge set, and the semantic mutation-stats counters -- must
match the host run exactly, across the whole serving scenario
catalogue (query-heavy, drift, churn-split with delete-triggered
cluster splits, ttl-drift).

The donation stress test drives seeded random insert/delete/predict
streams through the donated resident buffers and pins the mirror to
the host arrays after every mutation (a stale donated alias fails
immediately), then round-trips ``snapshot()``/``restore()`` -- the
device index must serialize exactly the host state.
"""

import io
import zlib

import numpy as np
import pytest

from repro.core.dbscan import grit_dbscan
from repro.data.scenarios import (get_churn_scenario,
                                  get_serving_scenario)
from repro.index import GritIndex, device_state

_DEFAULT_GATES = (device_state.MIN_FLAT_T, device_state.EDGE_MIN_FLAT_T)


@pytest.fixture(autouse=True)
def _force_kernel_path(monkeypatch):
    """Catalogue scenarios are CI-small, so under the production
    adaptive gates every delta stage would route to its host twin and
    the kernel path would go silently untested -- pin the gates to 0 so
    every stage dispatches.  ``test_adaptive_gates_differential``
    restores the defaults to cover the gated routing itself."""
    monkeypatch.setattr(device_state, "MIN_FLAT_T", 0)
    monkeypatch.setattr(device_state, "EDGE_MIN_FLAT_T", 0)

SERVING = ["query-heavy-3d", "drift-2d"]
CHURN = ["churn-split-2d", "ttl-drift-3d"]

# keys whose values are timing / device-internal telemetry, not
# semantics: everything else in a mutation stats dict must match the
# host run bit for bit (dist_evals differs because the device path
# spends float64 evals only on the uncertain band)
NONSEMANTIC = {"dist_evals", "t_total", "t_pack", "t_kernel",
               "band_fallback"}


def _seed(*key) -> int:
    return zlib.crc32("/".join(map(str, key)).encode())


def _fit_pair(pts, eps, min_pts, interpret=None):
    """The same fit twice: one host-serving index, one device-resident."""
    res = grit_dbscan(pts, eps, min_pts)
    host = GritIndex.from_fit(pts, eps, min_pts, res.labels,
                              core=res.core)
    dev = GritIndex.from_fit(pts, eps, min_pts, res.labels,
                             core=res.core)
    dev.ensure_device_state(interpret=interpret)
    return host, dev


def _assert_stats_match(sh, sd, where):
    for k in set(sh) | set(sd):
        if k in NONSEMANTIC:
            continue
        assert k in sh and k in sd, (where, k)
        assert np.array_equal(sh[k], sd[k]), (where, k, sh[k], sd[k])


def _assert_state_match(host, dev, where):
    assert np.array_equal(host.labels_arrival(), dev.labels_arrival()), where
    assert np.array_equal(host.core_arrival(), dev.core_arrival()), where
    he, de = host.merge_edges, dev.merge_edges
    if he is not None or de is not None:
        assert he is not None and de is not None, where
        assert np.array_equal(he, de), where
    mm = dev.device_state.mirror_matches(dev)
    assert all(mm.values()), (where, mm)


def _probe_queries(ss, pts, eps, seed):
    """Scenario queries + the adversarial cases the docstring promises:
    exact-eps boundary queries off real points, far out-of-bbox
    queries, and empty-cell queries between clusters."""
    rng = np.random.default_rng(seed)
    q = ss.query_batch(0, 64)
    d = pts.shape[1]
    base = pts[rng.integers(0, len(pts), 8)]
    axis = np.zeros((8, d))
    axis[:, 0] = eps                      # exactly eps along one axis
    boundary = base + axis
    span = pts.max(0) - pts.min(0)
    outside = pts.max(0)[None, :] + span[None, :] * (
        1.0 + rng.random((8, d)))         # far beyond the fitted bbox
    between = (pts.min(0) + pts.max(0))[None, :] / 2 + rng.normal(
        scale=span / 50, size=(8, d))     # likely-empty interior cells
    return np.concatenate([q, boundary, outside, between])


@pytest.mark.parametrize("name", SERVING)
def test_predict_differential(name):
    """Device predict == host predict, labels and d2 bit-identical,
    including eps-boundary / out-of-bbox / empty-cell queries."""
    ss = get_serving_scenario(name)
    pts = ss.fit_points()
    eps, mp = ss.base.eps, ss.base.min_pts
    host, dev = _fit_pair(pts, eps, mp)
    q = _probe_queries(ss, pts, eps, _seed("predict", name))
    lh, dh = host.predict(q, mode="host", return_d2=True)
    stats = {}
    ld, dd = dev.predict(q, mode="device", return_d2=True, stats=stats)
    assert np.array_equal(lh, ld)
    assert np.array_equal(dh, dd)                 # bitwise, inf included
    assert stats["mode"] == "device"
    assert stats["chunks"] >= 1
    # auto mode routes through the resident state once attached
    stats2 = {}
    la = dev.predict(q, stats=stats2)
    assert stats2["mode"] == "device"
    assert np.array_equal(la, lh)


@pytest.mark.parametrize("name", SERVING)
def test_serving_stream_differential(name):
    """Insert stream + interleaved predicts: states, stats and answers
    stay bit-identical step for step."""
    ss = get_serving_scenario(name)
    pts = ss.fit_points()
    eps, mp = ss.base.eps, ss.base.min_pts
    host, dev = _fit_pair(pts, eps, mp)
    for i, batch in enumerate(ss.insert_batches(0, 3)):
        sh = host.insert(batch)
        sd = dev.insert(batch)
        _assert_stats_match(sh, sd, (name, "insert", i))
        _assert_state_match(host, dev, (name, "insert", i))
        q = ss.query_batch(i, 32)
        lh, dh = host.predict(q, mode="host", return_d2=True)
        ld, dd = dev.predict(q, mode="device", return_d2=True)
        assert np.array_equal(lh, ld), (name, i)
        assert np.array_equal(dh, dd), (name, i)


@pytest.mark.parametrize("name", CHURN)
def test_churn_differential(name):
    """The churn catalogue (insert/delete plans incl. delete-triggered
    cluster splits and TTL expiry) through the device path: every op's
    stats and the full state match the host run exactly."""
    sc = get_churn_scenario(name)
    pts = sc.fit_points()
    eps, mp = sc.base.eps, sc.base.min_pts
    host, dev = _fit_pair(pts, eps, mp)
    for i, (op, arg) in enumerate(sc.ops(0)):
        if op == "insert":
            sh, sd = host.insert(arg), dev.insert(arg)
        else:
            sh, sd = host.delete(arg), dev.delete(arg)
        _assert_stats_match(sh, sd, (name, op, i))
        _assert_state_match(host, dev, (name, op, i))
    # merge graphs (built or maintained) agree at the end as well
    assert np.array_equal(host.ensure_merge_graph(),
                          dev.ensure_merge_graph())


def test_adaptive_gates_differential():
    """The production gate values route small delta stages to their
    host twins (``MIN_FLAT_T`` / ``EDGE_MIN_FLAT_T``); the gated mix of
    kernel and host stages must stay bit-identical too -- including the
    resident-flag sync the recompute gate performs after its host
    twin."""
    device_state.MIN_FLAT_T = _DEFAULT_GATES[0]
    device_state.EDGE_MIN_FLAT_T = _DEFAULT_GATES[1]
    sc = get_churn_scenario("churn-split-2d")
    pts = sc.fit_points()
    host, dev = _fit_pair(pts, sc.base.eps, sc.base.min_pts)
    for i, (op, arg) in enumerate(sc.ops(0)):
        sh, sd = (host.insert(arg), dev.insert(arg)) if op == "insert" \
            else (host.delete(arg), dev.delete(arg))
        _assert_stats_match(sh, sd, ("gated", op, i))
        _assert_state_match(host, dev, ("gated", op, i))
    q = sc.query_batch(0, 64) if hasattr(sc, "query_batch") else pts[:64]
    lh, dh = host.predict(q, mode="host", return_d2=True)
    ld, dd = dev.predict(q, mode="device", return_d2=True)
    assert np.array_equal(lh, ld) and np.array_equal(dh, dd)


@pytest.mark.parametrize("route", ["chunked", "host-routed"])
def test_chunked_and_host_routed_stages_differential(route, monkeypatch):
    """Stage sizes the catalogue cannot reach at CI scale: flat stages
    split into several ``FLAT_CHUNK`` dispatches, or routed whole to
    their host twin above ``MAX_FLAT_T``.  Both stay bit-identical."""
    if route == "chunked":
        monkeypatch.setattr(device_state, "FLAT_CHUNK", 256)
    else:
        monkeypatch.setattr(device_state, "MAX_FLAT_T", 0)
    sc = get_churn_scenario("churn-split-2d")
    pts = sc.fit_points()
    host, dev = _fit_pair(pts, sc.base.eps, sc.base.min_pts)
    chunks = []
    for i, (op, arg) in enumerate(sc.ops(0)):
        sh, sd = (host.insert(arg), dev.insert(arg)) if op == "insert" \
            else (host.delete(arg), dev.delete(arg))
        _assert_stats_match(sh, sd, (route, op, i))
        _assert_state_match(host, dev, (route, op, i))
        q = pts[i::7]
        stats = {}
        lh, dh = host.predict(q, mode="host", return_d2=True)
        ld, dd = dev.predict(q, mode="device", return_d2=True, stats=stats)
        assert np.array_equal(lh, ld) and np.array_equal(dh, dd), (route, i)
        chunks.append(stats.get("chunks", 0))
    if route == "chunked":
        assert max(chunks) > 1


def test_delete_split_differential():
    """An explicit bridge-cut: deleting the bridge points must split
    the cluster identically on both paths (the non-monotone case the
    persistent merge graph exists for)."""
    rng = np.random.default_rng(_seed("split"))
    eps, mp = 0.5, 4
    left = rng.normal(size=(60, 2), scale=0.3)
    right = rng.normal(size=(60, 2), scale=0.3) + [6.0, 0.0]
    bridge = np.stack([np.linspace(0.8, 5.2, 24),
                       np.zeros(24)], axis=1)
    bridge += rng.normal(scale=0.02, size=bridge.shape)
    pts = np.concatenate([left, right, bridge])
    host, dev = _fit_pair(pts, eps, mp)
    assert len(np.unique(host.labels[host.labels >= 0])) == 1
    bridge_ids = np.arange(120, 144)
    sh, sd = host.delete(bridge_ids), dev.delete(bridge_ids)
    _assert_stats_match(sh, sd, "split-delete")
    _assert_state_match(host, dev, "split-delete")
    lab = host.labels_arrival()
    assert len(np.unique(lab[lab >= 0])) == 2     # it really split


def _interleave(host, dev, pts, eps, steps, seed):
    """Seeded random insert/delete/predict stream applied to both
    indexes; asserts bit-equality after every op."""
    rng = np.random.default_rng(seed)
    d = pts.shape[1]
    lo, hi = pts.min(0), pts.max(0)
    for i in range(steps):
        op = rng.choice(["insert", "delete", "predict"],
                        p=[0.4, 0.3, 0.3])
        if op == "insert":
            m = int(rng.integers(3, 24))
            b = rng.uniform(lo - 2 * eps, hi + 2 * eps, size=(m, d))
            sh, sd = host.insert(b), dev.insert(b)
            _assert_stats_match(sh, sd, ("interleave", i))
        elif op == "delete":
            live = host.arrival_live()
            k = min(len(live), int(rng.integers(1, 16)))
            ids = rng.choice(live, k, replace=False)
            ids = np.concatenate([ids, [10 ** 9]])   # one bogus id
            sh, sd = host.delete(ids), dev.delete(ids)
            _assert_stats_match(sh, sd, ("interleave", i))
        else:
            m = int(rng.integers(4, 48))
            q = rng.uniform(lo - eps, hi + eps, size=(m, d))
            lh, dh = host.predict(q, mode="host", return_d2=True)
            ld, dd = dev.predict(q, mode="device", return_d2=True)
            assert np.array_equal(lh, ld), ("interleave", i)
            assert np.array_equal(dh, dd), ("interleave", i)
            continue
        _assert_state_match(host, dev, ("interleave", i))


def _stress_roundtrip(n, steps, seed):
    rng = np.random.default_rng(seed)
    pts = np.concatenate([
        rng.normal(size=(n // 2, 2), scale=0.4),
        rng.normal(size=(n // 2, 2), scale=0.4) + [3.0, 1.0]])
    eps, mp = 0.35, 4
    host, dev = _fit_pair(pts, eps, mp)
    _interleave(host, dev, pts, eps, steps, seed + 1)
    # snapshot/restore: the device index serializes exactly the host
    # state (resident buffers are derived, never snapshotted)
    sh, sd = host.snapshot(), dev.snapshot()
    assert set(sh) == set(sd)
    for k in sh:
        assert np.array_equal(sh[k], sd[k]), k
    buf = io.BytesIO()
    dev.save(buf)
    buf.seek(0)
    back = GritIndex.load(buf)
    assert back.device_state is None          # mirror is not shipped
    assert np.array_equal(back.labels_arrival(), host.labels_arrival())
    q = rng.uniform(-1, 4, size=(64, 2))
    assert np.array_equal(back.predict(q, mode="host"),
                          host.predict(q, mode="host"))
    # the restored index can re-attach a device state and keep serving
    back.ensure_device_state()
    assert np.array_equal(back.predict(q, mode="device"),
                          host.predict(q, mode="host"))
    return dev


def test_donated_buffer_stress_roundtrip():
    dev = _stress_roundtrip(n=160, steps=25, seed=_seed("stress"))
    ds = dev.device_state
    assert ds.donations > 0                   # scatters actually ran
    assert ds.uploads > 0


@pytest.mark.slow
def test_donated_buffer_stress_roundtrip_long():
    for rep in range(3):
        _stress_roundtrip(n=400, steps=120,
                          seed=_seed("stress-long", rep))


def test_interpret_mode_differential():
    """CPU-only runners: the same differential holds with the Pallas
    kernels forced through interpret mode."""
    ss = get_serving_scenario("drift-2d")
    pts = ss.fit_points()
    eps, mp = ss.base.eps, ss.base.min_pts
    host, dev = _fit_pair(pts, eps, mp, interpret=True)
    q = ss.query_batch(0, 48)
    assert np.array_equal(host.predict(q, mode="host"),
                          dev.predict(q, mode="device"))
    b = ss.insert_batches(0, 1)[0][:16]
    sh, sd = host.insert(b), dev.insert(b)
    _assert_stats_match(sh, sd, "interpret-insert")
    _assert_state_match(host, dev, "interpret-insert")


def test_compaction_refreshes_mirror():
    """Crossing compact_threshold re-packs the row layout: the mirror
    must follow (full re-upload) and serving must stay identical."""
    rng = np.random.default_rng(_seed("compact"))
    pts = rng.normal(size=(200, 2))
    host, dev = _fit_pair(pts, 0.4, 4)
    host.compact_threshold = dev.compact_threshold = 0.15
    ids = np.arange(0, 80)                    # 40% dead: triggers
    sh, sd = host.delete(ids), dev.delete(ids)
    assert sd["compacted"]
    _assert_stats_match(sh, sd, "compact")
    _assert_state_match(host, dev, "compact")
    assert dev.n == dev.n_live                # really re-packed
    q = rng.normal(size=(32, 2))
    assert np.array_equal(host.predict(q, mode="host"),
                          dev.predict(q, mode="device"))


def test_drop_device_state_falls_back():
    ss = get_serving_scenario("drift-2d")
    pts = ss.fit_points()
    host, dev = _fit_pair(pts, ss.base.eps, ss.base.min_pts)
    dev.drop_device_state()
    assert dev.device_state is None
    stats = {}
    q = ss.query_batch(0, 16)
    out = dev.predict(q, stats=stats)         # auto -> host on CPU
    assert stats["mode"] != "device"
    assert np.array_equal(out, host.predict(q, mode="host"))
