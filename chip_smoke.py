#!/usr/bin/env python3
"""Chip smoke: the clustering service's main path on one TPU chip.

    python chip_smoke.py              # one chip: fit -> index -> serving
    python chip_smoke.py --chips 4    # four chips: SPMD fit vs one-chip fit
    JAX_PLATFORMS=cpu python chip_smoke.py --n 20000   # CPU rehearsal

Workload (paper §5.1): ``seed_spreader(n, d=3, "simden")`` rounded onto
the paper's integer domain [0, 1e5]^3, eps = 200, MinPts = 100.  Integer
coordinates below 2^24 and their re-centred squared distances are exact
in float32, so the float32 device fit must agree with the float64 host
fit exactly: any difference is a defect, not a tolerance.

One chip, in one process, through the user entry points:

1. device report (platform, kind, count, versions; the kernel paths are
   neither forced to ``ref.py`` nor to the Pallas interpreter);
2. ``cluster(..., engine="device-kernels", return_index=True)``: adaptive
   attempts, cold and warm wall time, compiles and compile-cache hits,
   whether the fit program holds a ``tpu_custom_call`` (the Pallas
   plane), peak device bytes;
3. the host float64 ``grit`` fit of the same points: identical core
   flags, labels conformant (``assert_labels_conformant``);
4. serving: an ``auto``-mode predict before any resident state (the
   Pallas ``row_min_batch`` route on TPU) against the host predict, then
   ``ClusterServer(index, device_state=True)`` -- 8 predicts of 4,096
   queries, one insert of 4,096 points and one delete of a 4,096-point
   spatial block -- replayed on a host
   twin restored from the fit's snapshot; every predict stream and the
   final labels must be bit-identical.

``--chips 4`` runs only the distributed fit on a 4-chip ``("shard",)``
mesh (Pallas plane on) and compares it with the one-chip fit of the
same points by the same exact comparison.

Each phase prints its findings on lines of its own.  The last line of
stdout is one JSON object, ``{"ok": ..., "device": {"platform",
"kind", "count"}}``; ``ok`` is true only on a TPU with every check
passed, and the exit code is 0 only then.  Off the TPU the phases run
only as a sized rehearsal (``--n``); otherwise the script stops after
the device report.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from importlib import metadata
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

EPS = 200.0
MIN_PTS = 100
N_FULL = 1_000_000
BATCH = 4096          # queries per predict request, inserts, deletes
N_PREDICTS = 8


class Checks:
    """Named pass/fail checks; every one is printed as it is made."""

    def __init__(self):
        self.failed = []

    def __call__(self, name: str, cond: bool, detail: str = "") -> bool:
        cond = bool(cond)
        print(f"[check] {name}: {'PASS' if cond else 'FAIL'}"
              + (f" ({detail})" if detail else ""), flush=True)
        if not cond:
            self.failed.append(name)
        return cond


def _version(pkg: str) -> str:
    try:
        return metadata.version(pkg)
    except metadata.PackageNotFoundError:
        return "not installed"


def _compile_stats():
    """(backend compiles, their seconds, persistent-cache hits) so far."""
    from repro import obs
    snap = obs.registry().snapshot()
    dur = snap.get("jax.dur.jax.core.compile.backend_compile_duration",
                   {"count": 0, "sum": 0.0})
    return (int(dur["count"]), float(dur["sum"]),
            int(snap.get("jax.events.jax.compilation_cache.cache_hits", 0)))


def _print_compiles(tag: str, before) -> None:
    c, s, h = _compile_stats()
    print(f"[{tag}] compiles={c - before[0]} compile_s={s - before[1]} "
          f"cache_hits={h - before[2]}", flush=True)


def _peak_bytes(dev):
    stats = dev.memory_stats()
    if not stats or "peak_bytes_in_use" not in stats:
        return "not reported"
    return int(stats["peak_bytes_in_use"])


def make_points(n: int, seed: int) -> np.ndarray:
    from repro.data.seed_spreader import seed_spreader
    return np.rint(seed_spreader(n=n, d=3, variant="simden", seed=seed))


def make_queries(points: np.ndarray, m: int,
                 rng: np.random.Generator) -> np.ndarray:
    """3/4 fit points jittered by up to ~eps, 1/4 uniform noise over
    the domain; integer-valued like the fit points."""
    k = 3 * m // 4
    near = points[rng.integers(0, len(points), k)] \
        + rng.normal(scale=EPS / 2, size=(k, points.shape[1]))
    far = rng.uniform(0, 1e5, size=(m - k, points.shape[1]))
    return np.rint(np.concatenate([near, far]))


def phase_device(checks):
    import jax
    from repro.kernels import ops as kernel_ops

    devs = jax.devices()
    dev = devs[0]
    print(f"[device] platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devs)} jax={jax.__version__} "
          f"jaxlib={_version('jaxlib')} libtpu={_version('libtpu')}",
          flush=True)
    checks("kernel paths not forced to ref.py or the interpreter",
           not kernel_ops.FORCE_REF and not kernel_ops.FORCE_INTERPRET,
           f"FORCE_REF={kernel_ops.FORCE_REF} "
           f"FORCE_INTERPRET={kernel_ops.FORCE_INTERPRET}")
    return dev, len(devs)


def phase_fit(points, dev, checks):
    import jax
    import jax.numpy as jnp
    from repro.core.device_dbscan import GritCaps, device_dbscan
    from repro.engine import cluster

    before = _compile_stats()
    t0 = time.perf_counter()
    res = cluster(points, EPS, MIN_PTS, engine="device-kernels",
                  return_index=True)
    cold = time.perf_counter() - t0
    for i, a in enumerate(res.attempts):
        c = a["caps"]
        print(f"[fit] attempt {i + 1}: overflow={list(a['overflow'])} "
              f"grid_cap={c['grid_cap']} frontier_cap={c['frontier_cap']} "
              f"k_cap={c['k_cap']} c_cap={c['c_cap']} m_cap={c['m_cap']} "
              f"pair_cap={c['pair_cap']} use_kernels={c['use_kernels']}",
              flush=True)
    print(f"[fit] cold_s={cold} (engine {res.stats['t_total']} s, index "
          f"build {cold - res.stats['t_total']} s)", flush=True)
    _print_compiles("fit", before)

    t0 = time.perf_counter()
    warm = cluster(points, EPS, MIN_PTS, engine="device-kernels")
    warm_s = time.perf_counter() - t0
    caps = GritCaps(**res.attempts[-1]["caps"])
    n_pad = res.stats["n_padded"]
    padded = np.zeros((n_pad, points.shape[1]), np.float32)
    padded[:len(points)] = points
    pts = jnp.asarray(padded)
    valid = jnp.asarray(np.arange(n_pad) < len(points))
    t0 = time.perf_counter()
    jax.block_until_ready(device_dbscan(pts, EPS, MIN_PTS, caps,
                                        point_valid=valid))
    program_s = time.perf_counter() - t0
    print(f"[fit] warm_s={warm_s} (engine {warm.stats['t_total']} s) "
          f"program_warm_s={program_s} clusters={res.n_clusters} "
          f"core={int(res.core.sum())} noise={int((res.labels < 0).sum())}",
          flush=True)
    print(f"[fit] peak_bytes_in_use={_peak_bytes(dev)}", flush=True)
    text = device_dbscan.lower(
        jax.ShapeDtypeStruct(pts.shape, pts.dtype), EPS, MIN_PTS, caps,
        point_valid=jax.ShapeDtypeStruct(valid.shape, valid.dtype)
    ).as_text()
    checks("fit program contains a tpu_custom_call (Pallas plane)",
           "tpu_custom_call" in text)
    checks("cold and warm fits agree",
           np.array_equal(res.labels, warm.labels)
           and np.array_equal(res.core, warm.core))
    return res


def compare_exact(points, ref, got, checks, what: str) -> None:
    """Phase 3's comparison: identical core flags, conformant labels."""
    from repro.core.validate import assert_labels_conformant

    diff = int((ref.core != got.core).sum())
    checks(f"{what}: core flags identical", diff == 0,
           f"{diff} differ of {len(points)}")
    try:
        t0 = time.perf_counter()
        assert_labels_conformant(points, EPS, MIN_PTS, ref.labels,
                                 got.labels, core=ref.core)
        checks(f"{what}: labels conformant", True,
               f"{time.perf_counter() - t0:.1f} s")
    except AssertionError as e:
        checks(f"{what}: labels conformant", False, str(e)[:300])
    checks(f"{what}: cluster count equal", ref.n_clusters == got.n_clusters,
           f"{ref.n_clusters} vs {got.n_clusters}")


def phase_reference(points, fit, checks):
    from repro.engine import cluster

    t0 = time.perf_counter()
    ref = cluster(points, EPS, MIN_PTS, engine="grit")
    print(f"[reference] host grit f64 fit_s={time.perf_counter() - t0} "
          f"clusters={ref.n_clusters} core={int(ref.core.sum())}",
          flush=True)
    compare_exact(points, ref, fit, checks, "device-kernels vs grit f64")


def phase_serve(points, index, seed, checks):
    from repro import obs
    from repro.index import GritIndex
    from repro.serve.driver import ClusterServer

    rng = np.random.default_rng(seed + 1)
    # the first mutation's merge-graph build is a whole-index host pass;
    # building it before the snapshot lets the twin restore it instead
    # of paying it a second time
    t0 = time.perf_counter()
    edges = index.ensure_merge_graph()
    print(f"[serve] merge graph edges={len(edges)} "
          f"build_s={time.perf_counter() - t0}", flush=True)
    twin = GritIndex.restore(index.snapshot())

    q = make_queries(points, BATCH, rng)
    st = {}
    t0 = time.perf_counter()
    got = index.predict(q, mode="auto", stats=st)
    print(f"[serve] auto predict mode={st['mode']} queries={len(q)} "
          f"s={time.perf_counter() - t0} caps={st.get('caps')}", flush=True)
    checks("auto predict takes the kernel route", st["mode"] == "kernel")
    checks("auto predict equals host predict",
           np.array_equal(got, twin.predict(q, mode="host")))

    reg = obs.registry()
    names = ("kernels.dispatch.pairwise_d2_flat",
             "kernels.dispatch.pairwise_d2_flat_res")
    flat0 = [reg.counter(k).value for k in names]
    dev_srv = ClusterServer(index, slots=1, query_cap=BATCH,
                            device_state=True)
    host_srv = ClusterServer(twin, slots=1, query_cap=BATCH, mode="host")
    # erase one spatial block (the fit points nearest a random one): a
    # delete spread over every grid would recount the whole index
    centre = points[rng.integers(len(points))]
    deletes = np.argsort(((points - centre) ** 2).sum(1))[:BATCH]
    inserts = make_queries(points, BATCH, rng)
    for i in range(N_PREDICTS):
        q = make_queries(points, BATCH, rng)
        for srv in (dev_srv, host_srv):
            srv.submit(q)
            if i == 2:
                srv.submit_insert(inserts)
            if i == 5:
                srv.submit_delete(deletes)
    for name, srv in (("device", dev_srv), ("host", host_srv)):
        t0 = time.perf_counter()
        srv.run()
        s = srv.summary()
        print(f"[serve] {name} server: requests={s['requests']} "
              f"queries={s['queries']} inserted={s['inserted']} "
              f"deleted={s['deleted']} run_s={time.perf_counter() - t0} "
              f"latency_ms_p50={s['latency_ms_p50']}", flush=True)
    flat = [reg.counter(k).value - v for k, v in zip(names, flat0)]
    fallback = sum(r.result.get("band_fallback", 0) for r in dev_srv.done
                   if r.result is not None)
    uncertain = sum(st["predict"].get("uncertain", 0)
                    for st in dev_srv.step_log)
    print(f"[serve] pairwise_d2_flat={flat[0]} pairwise_d2_flat_res="
          f"{flat[1]} band_fallback={fallback} predict_uncertain="
          f"{uncertain}", flush=True)
    checks("both flat dispatch counters above zero", min(flat) > 0)
    same = [np.array_equal(a.labels, b.labels)
            for a, b in zip(dev_srv.done, host_srv.done)
            if a.kind == "predict"]
    checks("every device predict stream bit-identical to the host twin",
           len(same) == N_PREDICTS and all(same),
           f"{sum(same)}/{N_PREDICTS}")
    checks("final labels_arrival bit-identical to the host twin",
           np.array_equal(index.labels_arrival(), twin.labels_arrival())
           and np.array_equal(index.core_arrival(), twin.core_arrival()))


def phase_four_chips(points, checks):
    import jax
    from repro.engine import cluster

    devs = jax.devices()
    if not checks("four devices visible", len(devs) == 4,
                  f"{len(devs)} visible"):
        return
    mesh = jax.make_mesh((4,), ("shard",))
    before = _compile_stats()
    t0 = time.perf_counter()
    dist = cluster(points, EPS, MIN_PTS, engine="distributed", mesh=mesh,
                   use_kernels=True)
    print(f"[dist] cold_s={time.perf_counter() - t0} "
          f"shards={dist.stats['n_shards']} "
          f"use_kernels={dist.stats['use_kernels']} "
          f"attempts={[list(a['overflow']) for a in dist.attempts]}",
          flush=True)
    _print_compiles("dist", before)
    t0 = time.perf_counter()
    warm = cluster(points, EPS, MIN_PTS, engine="distributed", mesh=mesh,
                   use_kernels=True)
    print(f"[dist] warm_s={time.perf_counter() - t0}", flush=True)
    checks("cold and warm distributed fits agree",
           np.array_equal(dist.labels, warm.labels)
           and np.array_equal(dist.core, warm.core))
    peaks = [_peak_bytes(d) for d in devs]
    print(f"[dist] peak_bytes_in_use per device={peaks}", flush=True)
    t0 = time.perf_counter()
    one = cluster(points, EPS, MIN_PTS, engine="device-kernels")
    print(f"[dist] one-chip fit_s={time.perf_counter() - t0} on "
          f"{devs[0]}", flush=True)
    compare_exact(points, one, dist, checks, "4-chip vs 1-chip fit")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--n", type=int, default=None,
                    help=f"points (default {N_FULL}); lower it for a "
                         f"rehearsal off the chip")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro import compile_cache, obs

    cache = compile_cache.enable()
    obs.install_jax_hooks()
    checks = Checks()
    dev, count = phase_device(checks)
    print(f"[cache] dir={cache}", flush=True)
    on_tpu = dev.platform == "tpu"
    want = args.chips

    ok = False
    if on_tpu or args.n is not None:
        try:
            t0 = time.perf_counter()
            points = make_points(args.n or N_FULL, args.seed)
            print(f"[data] n={len(points)} d={points.shape[1]} eps={EPS} "
                  f"min_pts={MIN_PTS} seed={args.seed} "
                  f"gen_s={time.perf_counter() - t0}", flush=True)
            if args.chips == 4:
                phase_four_chips(points, checks)
            else:
                fit = phase_fit(points, dev, checks)
                phase_reference(points, fit, checks)
                phase_serve(points, fit.index, args.seed, checks)
            _print_compiles("total", (0, 0.0, 0))
            ok = not checks.failed
        except Exception:
            traceback.print_exc()
            print("[error] a phase raised; see the traceback on stderr",
                  flush=True)
    else:
        print("[device] no TPU: pass --n to rehearse the phases here",
              flush=True)
    if checks.failed:
        print(f"[summary] failed checks: {checks.failed}", flush=True)
    ok = ok and on_tpu and count == want
    print(json.dumps({"ok": ok, "device": {"platform": dev.platform,
                                           "kind": dev.device_kind,
                                           "count": count}}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
