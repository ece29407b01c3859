"""Observability-plane tests: the ``repro.obs`` tracer / metrics /
export contract.

Pins the four invariants the plane is built on:

* **Disabled tracer is free** -- ``span()`` returns the shared no-op
  object (zero events, zero allocations), and the obs package itself is
  clean under the ``repro.analysis`` hot-path-sync rule with exactly
  the one justified pragma at the enabled-mode span close.
* **Chrome trace export round-trips** -- the exported document is valid
  JSON in trace-event shape, ``load_trace`` recovers the events, and
  interval-containment nesting reconstructs the lexical entry/exit
  order the spans were recorded with.
* **Counter registry loses nothing under the serve driver** -- the
  double-buffered step (predict dispatch for batch k+1 overlapping
  resolve of batch k) must account every request/query exactly once,
  and ``summary()`` stays a faithful view over the registry.
* **Provenance stamps are complete** -- ``bench_meta()`` carries the
  fields that make a measurement comparable across machines.
"""

import json

import numpy as np
import pytest

from repro import obs
from repro.obs import view as obs_view
from repro.obs.export import load_trace, write_chrome_trace, write_jsonl
from repro.obs.metrics import MetricsRegistry


@pytest.fixture
def tracer():
    """Fresh enabled tracer, restored to prior state afterwards."""
    was = obs.enabled()
    t = obs.enable(clear=True)
    yield t
    if not was:
        obs.disable()


# ---------------------------------------------------------------------------
# disabled-tracer invariant
# ---------------------------------------------------------------------------

def test_disabled_tracer_is_shared_noop(monkeypatch, tmp_path):
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    was = obs.enabled()
    obs.disable()
    try:
        assert not TraceAnnotation.is_enabled()   # profiler idle too
        s1 = obs.span("anything", n=3)
        s2 = obs.span("else")
        assert s1 is s2 is obs.NOOP_SPAN
        # reentrant, chainable, recordless
        with obs.span("outer") as sp:
            assert sp.set(k=1) is sp
            assert sp.sync(object()) is sp
            with obs.span("inner"):
                pass
        assert obs.get_tracer() is None
        assert not obs.enabled()

        # profiler only: an annotation, and never a sync, even with
        # device values registered; the same spy sees the tracer-on
        # close sync, so it would see one here
        x = jnp.ones(4)
        jax.block_until_ready(x)
        syncs = []
        monkeypatch.setattr(jax, "block_until_ready",
                            lambda *a, **k: syncs.append(a))
        jax.profiler.start_trace(str(tmp_path))
        try:
            sp = obs.span("profiled", sync=x, n=3)
            assert sp is not obs.NOOP_SPAN
            with sp:
                assert sp.sync(x) is sp and sp.set(k=1) is sp
            assert syncs == [] and obs.get_tracer() is None
            obs.enable(clear=True)
            with obs.span("traced", sync=x):
                pass
            assert len(syncs) == 1
            obs.disable()
        finally:
            jax.profiler.stop_trace()
        assert obs.span("after") is obs.NOOP_SPAN
    finally:
        if was:
            obs.enable()
        else:
            obs.disable()


def test_obs_package_clean_under_hot_path_sync_rule():
    """The obs package is *not* excluded from the repo linter: with
    tracing wired through the serving stack, ``src`` must still be
    clean under every rule, and the tracer's one enabled-mode sync
    site carries its justified pragma."""
    import os
    from repro.analysis import analyze_paths

    pkg = os.path.dirname(obs.__file__)
    src = os.path.dirname(os.path.dirname(pkg))
    report = analyze_paths([src])
    assert not report.active, [(v.rule, v.path) for v in report.active]
    with open(os.path.join(pkg, "trace.py")) as f:
        text = f.read()
    assert "block_until_ready" in text
    assert "grit-lint: disable=hot-path-sync --" in text


# ---------------------------------------------------------------------------
# spans + chrome export round-trip
# ---------------------------------------------------------------------------

def _record_nested(tracer):
    with obs.span("fit", n=100):
        with obs.span("pack"):
            pass
        with obs.span("cluster"):
            with obs.span("kernel", bucket=256):
                pass
        with obs.span("unpack"):
            pass
    return tracer.snapshot_events()


def test_span_events_record_entry_exit_order(tracer):
    events = _record_nested(tracer)
    # complete events append at *exit*: children precede the parent
    assert [e["name"] for e in events] == [
        "pack", "kernel", "cluster", "unpack", "fit"]
    by = {e["name"]: e for e in events}
    assert by["fit"]["depth"] == 0
    assert by["pack"]["depth"] == by["cluster"]["depth"] == 1
    assert by["kernel"]["depth"] == 2
    assert by["fit"]["args"] == {"n": 100}
    # containment: every child interval sits inside its parent's
    for child, parent in [("pack", "fit"), ("cluster", "fit"),
                          ("kernel", "cluster")]:
        c, p = by[child], by[parent]
        assert c["ts"] >= p["ts"]
        assert c["ts"] + c["dur"] <= p["ts"] + p["dur"] + 1e-6


def test_chrome_trace_roundtrip_and_nesting(tracer, tmp_path):
    events = _record_nested(tracer)
    path = tmp_path / "trace.json"
    write_chrome_trace(str(path), events,
                       metrics={"k.count": 3}, meta={"git_rev": "abc"})
    doc = json.loads(path.read_text())           # valid JSON
    assert {"traceEvents", "displayTimeUnit", "otherData"} <= set(doc)
    assert all(e["ph"] == "X" and e["dur"] >= 0.0
               for e in doc["traceEvents"])
    got, metrics, meta = load_trace(str(path))
    assert [e["name"] for e in got] == [e["name"] for e in events]
    assert metrics == {"k.count": 3} and meta == {"git_rev": "abc"}
    # viewer reconstructs the lexical nesting from intervals alone
    parents = {e["name"]: e["parent"] for e in obs_view._nest(got)}
    assert parents == {"fit": None, "pack": "fit", "cluster": "fit",
                       "kernel": "cluster", "unpack": "fit"}


def test_jsonl_roundtrip(tracer, tmp_path):
    events = _record_nested(tracer)
    path = tmp_path / "trace.jsonl"
    write_jsonl(str(path), events, metrics={"c": 1},
                meta={"git_rev": "abc"})
    got, metrics, meta = load_trace(str(path))
    assert [e["name"] for e in got] == [e["name"] for e in events]
    assert metrics == {"c": 1} and meta["git_rev"] == "abc"


def test_attribution_and_view_cli(tracer, tmp_path, capsys):
    events = _record_nested(tracer)
    att = obs_view.attribution(events, root="fit")
    assert set(att["children"]) == {"pack", "cluster", "unpack"}
    assert 0.0 < att["coverage"] <= 1.0 + 1e-9
    path = tmp_path / "trace.json"
    write_chrome_trace(str(path), events, metrics={"adaptive.retries": 2})
    assert obs_view.main([str(path), "--root", "fit"]) == 0
    out = capsys.readouterr().out
    assert "attribution of 'fit'" in out
    assert "adaptive.retries" in out


def test_span_error_path_still_records(tracer):
    with pytest.raises(ValueError):
        with obs.span("boom"):
            raise ValueError("x")
    (ev,) = tracer.snapshot_events()
    assert ev["name"] == "boom" and ev["args"]["error"] is True


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_registry_counter_gauge_histogram():
    reg = MetricsRegistry()
    reg.counter("c").inc()
    reg.counter("c").inc(4)
    reg.gauge("g").set(0.25)
    h = reg.histogram("h")
    vals = [3.0, 1.0, 4.0, 1.0, 5.0, 9.0, 2.0, 6.0]
    for v in vals:
        h.observe(v)
    assert reg.counter("c").value == 5
    assert reg.gauge("g").value == 0.25
    assert h.count == len(vals) and h.total == sum(vals)
    for q in (50, 95, 99):
        assert h.percentile(q) == pytest.approx(np.percentile(vals, q))
    snap = reg.snapshot()
    assert snap["c"] == 5
    reg.reset()
    assert reg.counter("c").value == 0


def test_bench_meta_provenance_keys():
    meta = obs.bench_meta()
    for k in ("timestamp", "python", "platform", "git_rev", "jax",
              "backend", "device_count"):
        assert k in meta, k
    json.dumps(meta)                              # JSON-able


# ---------------------------------------------------------------------------
# serve driver: no lost increments under the double-buffered step
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def served_index():
    from repro.data.scenarios import get_serving_scenario
    from repro.engine import cluster

    ss = get_serving_scenario("query-heavy-3d")
    res = cluster(ss.fit_points(), ss.base.eps, ss.base.min_pts,
                  engine="grit", return_index=True)
    return ss, res.index


def test_serve_counters_account_every_request(served_index):
    from repro.serve import ClusterServer

    ss, idx = served_index
    sizes = [7, 31, 2, 18, 25, 13, 9, 4]
    rng = np.random.default_rng(3)
    q = ss.query_batch(seed=3, n=int(sum(sizes)))
    srv = ClusterServer(idx, slots=3, mode="host")
    off = 0
    for m in sizes:
        srv.submit(q[off:off + m])
        off += m
    done = srv.run()
    reg = srv.metrics
    assert reg.counter("serve.requests").value == len(sizes) == len(done)
    assert reg.counter("serve.queries").value == sum(sizes)
    assert reg.counter("serve.steps").value == len(srv.step_log)
    assert reg.histogram("serve.latency_ms").count == len(sizes)
    qw = reg.histogram("serve.queue_wait_ms")
    assert qw.count == len(sizes)
    assert all(s["queue_wait_ms"] >= 0.0 for s in srv.step_log)

    s = srv.summary()
    # summary is a *view* over the registry: same books, old keys intact
    assert s["requests"] == len(sizes) and s["queries"] == sum(sizes)
    lat = reg.histogram("serve.latency_ms")
    assert s["latency_ms_p50"] == pytest.approx(lat.percentile(50))
    assert s["latency_ms_p99"] == pytest.approx(lat.percentile(99))
    assert s["queue_wait_ms_p50"] == pytest.approx(qw.percentile(50))
    assert s["latency_ms_p50"] <= s["latency_ms_p95"] \
        <= s["latency_ms_p99"]


def test_serve_counters_survive_tracing_toggle(served_index):
    """Tracing on must not change the request/query accounting."""
    from repro.serve import ClusterServer

    ss, idx = served_index
    was = obs.enabled()
    obs.enable(clear=True)
    try:
        srv = ClusterServer(idx, slots=2, mode="host")
        for seed in range(5):
            srv.submit(ss.query_batch(seed=seed, n=6))
        srv.run()
        assert srv.metrics.counter("serve.requests").value == 5
        assert srv.metrics.counter("serve.queries").value == 30
        names = {e["name"] for e in obs.get_tracer().snapshot_events()}
        assert "serve.step" in names
        assert "serve.step.dispatch" in names
    finally:
        if not was:
            obs.disable()


# ---------------------------------------------------------------------------
# the fit's stages: profiler-bridged spans, stage histograms, device scopes
# ---------------------------------------------------------------------------

STAGES = ("engine.device.prepare", "engine.census", "engine.device.attempt",
          "engine.device.fetch")
TIMED = ("engine.device.prepare", "engine.census", "engine.device.fetch")
SCOPES = ("grit.grids", "grit.neighbors", "grit.core", "grit.core/tier1",
          "grit.core/tier2", "grit.core/tier3", "grit.merge",
          "grit.components", "grit.border", "grit.border/tier1",
          "grit.border/tier2", "grit.border/tier3", "grit.output")


@pytest.fixture(scope="module")
def profiled_fit(tmp_path_factory):
    """Two ``cluster(engine="device-kernels")`` calls on 3,000 points,
    tracing off; the second under the JAX profiler.  Returns the result,
    the host events of the profiler's ``.xplane.pb`` named after the
    fit's spans, and the stage histograms' counts before and after."""
    import glob

    import jax
    from jax.profiler import ProfileData
    from repro.engine import cluster

    rng = np.random.default_rng(7)
    centers = rng.uniform(0, 400, (6, 3))
    pts = np.concatenate([c + rng.normal(0, 12, (500, 3)) for c in centers])
    was = obs.enabled()
    obs.disable()
    reg = obs.registry()
    before = {n: reg.histogram(f"{n}_s").count for n in TIMED}
    try:
        cluster(pts, 20.0, 10, engine="device-kernels")
        tdir = tmp_path_factory.mktemp("xplane")
        jax.profiler.start_trace(str(tdir))
        try:
            res = cluster(pts, 20.0, 10, engine="device-kernels")
        finally:
            jax.profiler.stop_trace()
    finally:
        if was:
            obs.enable()
    after = {n: reg.histogram(f"{n}_s").count for n in TIMED}
    path = glob.glob(f"{tdir}/**/*.xplane.pb", recursive=True)[0]
    events = [(plane.name, line.name, e.name, e.start_ns,
               e.start_ns + e.duration_ns)
              for plane in ProfileData.from_file(path).planes
              for line in plane.lines for e in line.events
              if e.name in STAGES + ("engine.cluster",)]
    return res, events, before, after


def test_fit_stage_spans_reach_the_profiler_trace(profiled_fit):
    """``engine.cluster`` holds every stage span on the host thread's
    line of the trace, one attempt span per adaptive attempt."""
    res, events, _, _ = profiled_fit
    roots = [e for e in events if e[2] == "engine.cluster"]
    assert len(roots) == 1
    plane, line, _, lo, hi = roots[0]
    assert not plane.startswith("/device")
    inside = [e for e in events if e[2] in STAGES]
    assert {e[2] for e in inside} == set(STAGES)
    for e in inside:
        assert (e[0], e[1]) == (plane, line) and lo <= e[3] <= e[4] <= hi
    assert sum(e[2] == "engine.device.attempt" for e in inside) == \
        len(res.attempts)


def test_fit_stage_histograms_and_coverage(profiled_fit):
    """One observation per stage histogram per ``cluster()`` call, and
    the stage spans cover at least 90 % of ``engine.cluster``."""
    _, events, before, after = profiled_fit
    assert all(after[n] - before[n] == 2 for n in TIMED), (before, after)
    (lo, hi), = [(e[3], e[4]) for e in events if e[2] == "engine.cluster"]
    staged = sum(e[4] - e[3] for e in events if e[2] in STAGES)
    assert staged >= 0.9 * (hi - lo), (staged, hi - lo)


def test_device_dbscan_phases_are_named_in_compiled_hlo(profiled_fit):
    """Every ``grit.*`` phase scope reaches the compiled program's
    ``op_name`` metadata (the packed tier sweeps nested under their
    phase)."""
    import re

    import jax.numpy as jnp
    from repro.core.device_dbscan import GritCaps, device_dbscan

    res, _, _, _ = profiled_fit
    caps = GritCaps(**res.attempts[-1]["caps"])
    n = res.stats["n_padded"]
    text = device_dbscan.lower(
        jnp.zeros((n, 3), jnp.float32), 20.0, 10, caps,
        point_valid=jnp.zeros((n,), bool)).compile().as_text()
    found = re.findall(
        r'op_name="jit\(device_dbscan\)/((grit\.[a-z]+)(?:/tier\d)?)', text)
    assert {name for pair in found for name in pair} == set(SCOPES)


def test_tracing_does_not_change_the_distributed_program(monkeypatch):
    """A traced distributed fit runs the untraced fit's one SPMD step:
    same answers, one compiled program, and only the fused step's span
    on the host -- a trace times the program users run."""
    import jax
    from jax.sharding import Mesh

    from repro.dist import step as dist_step
    from repro.engine import cluster
    from repro.obs import trace as obs_trace

    rng = np.random.default_rng(17)
    pts = np.concatenate([rng.normal(c, 1.5, (400, 2))
                          for c in ((0, 0), (20, 5), (8, 25))])
    mesh = Mesh(np.array(jax.devices()[:1]), ("shard",))
    monkeypatch.setattr(dist_step, "_STEP_CACHE", {})
    # monkeypatch restores the process tracer (on or off) afterwards
    monkeypatch.setattr(obs_trace, "_TRACER", None)

    plain = cluster(pts, 2.0, 5, engine="distributed", mesh=mesh)
    obs.enable()
    traced = cluster(pts, 2.0, 5, engine="distributed", mesh=mesh)
    names = {e["name"] for e in obs.get_tracer().snapshot_events()}

    assert plain.n_clusters == 3
    assert np.array_equal(plain.labels, traced.labels)
    assert np.array_equal(plain.core, traced.core)
    assert plain.overflow == traced.overflow
    assert plain.attempts == traced.attempts
    assert len(dist_step._STEP_CACHE) == 1
    assert "dist.fit.spmd_step" in names
    assert "dist.fit.halo_exchange" not in names
