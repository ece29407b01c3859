"""Quickstart: exact GriT-DBSCAN through the unified engine API.

    PYTHONPATH=src python examples/quickstart.py

One entry point (``repro.engine.cluster``) drives every backend: the
paper-faithful host pipeline, the LDF variant, and the fully in-graph
device pipeline with adaptive static caps.  All are verified equivalent
to the O(n^2) oracle.  The last sections show the fit-once / serve-many
path: ``return_index=True`` keeps the fitted ``GritIndex``, which
snapshots to flat arrays, restores in another process, and serves the
full mutation plane -- point queries, micro-batch inserts, exact
deletes and compaction -- without ever refitting; and the sharded
variant (``fit_sharded`` -> ``ShardedGritIndex``): a distributed fit
kept as per-slab index shards plus a global label map, serving
slab-routed predicts and cross-shard inserts/deletes the same way.
"""

import io
import time

import numpy as np

from repro.data.seed_spreader import seed_spreader
from repro.engine import cluster, engine_descriptions
from repro.core.validate import assert_dbscan_equivalent
from repro.index import GritIndex, ShardedGritIndex, fit_sharded


def main():
    n, d = 4000, 3
    eps, min_pts = 3500.0, 10
    print(f"generating {n} points in {d}-D (seed-spreader, varden)...")
    pts = seed_spreader(n, d, variant="varden", restarts=6, seed=0)

    print("registered engines:")
    for name, desc in engine_descriptions().items():
        print(f"  {name:12s} {desc.splitlines()[0]}")

    print("\nGriT-DBSCAN (paper Algorithm 6, grid tree + FastMerging):")
    r = cluster(pts, eps, min_pts, engine="grit")
    s = r.stats
    print(f"  clusters={r.n_clusters}  grids={s['num_grids']}  "
          f"kappa_max={s.get('merge_max_iters', 0)}  "
          f"merge dist evals={s.get('merge_dist_evals', 0):,}")
    print(f"  time: partition {s['t_partition']*1e3:.1f}ms  "
          f"neighbors {s['t_neighbors']*1e3:.1f}ms  "
          f"cores {s['t_cores']*1e3:.1f}ms  merge {s['t_merge']*1e3:.1f}ms  "
          f"assign {s['t_assign']*1e3:.1f}ms")

    print("GriT-DBSCAN-LDF (union-find, low-density-first):")
    r_ldf = cluster(pts, eps, min_pts, engine="grit-ldf")
    print(f"  clusters={r_ldf.n_clusters}  "
          f"merge checks={r_ldf.stats['merge_checks']} "
          f"(vs {s['merge_checks']} for BFS order)")

    print("device pipeline (single jitted XLA program, adaptive caps):")
    r_dev = cluster(pts, eps, min_pts, engine="device")
    trail = " -> ".join(str(a["overflow"] or "ok") for a in r_dev.attempts)
    print(f"  clusters={r_dev.n_clusters}  "
          f"cap attempts: {trail}  "
          f"(caps estimated from grid stats, no hand tuning)")

    print("validating all three against the O(n^2) oracle...")
    ref = cluster(pts, eps, min_pts, engine="brute")
    for res in (r, r_ldf, r_dev):
        assert_dbscan_equivalent(pts, eps, min_pts, ref.labels, res.labels)
    print("all equivalent.")

    print("\nfit once, serve many (the GritIndex serving plane):")
    fitted = cluster(pts, eps, min_pts, engine="grit", return_index=True)
    buf = io.BytesIO()
    fitted.index.save(buf)                # flat arrays: ships anywhere
    buf.seek(0)
    idx = GritIndex.load(buf)             # e.g. in another process
    rng = np.random.default_rng(1)
    queries = pts[rng.integers(0, n, 500)] + rng.normal(
        scale=0.2 * eps, size=(500, d))
    t0 = time.perf_counter()
    labels = idx.predict(queries)         # exact: nearest-core-within-eps
    t_pred = time.perf_counter() - t0
    print(f"  snapshot {buf.getbuffer().nbytes / 1e3:.0f}kB -> restore -> "
          f"predict 500 queries in {t_pred * 1e3:.1f}ms "
          f"({int((labels >= 0).sum())} assigned, "
          f"{int((labels < 0).sum())} noise) -- no refit")
    st = idx.insert(queries[:64])         # micro-batch incremental update
    print(f"  insert 64 points: {st['newly_core']} newly core, "
          f"{st['affected_grids']} grids recomputed, "
          f"{st['t_total'] * 1e3:.1f}ms")
    # the full mutation plane: fit -> insert -> delete -> compact.
    # deletes are by arrival id (fit points are 0..n-1, inserts append;
    # ids are never reused) and are exact even where DBSCAN is
    # non-monotone -- cutting a bridge splits the cluster, and the
    # persistent merge graph makes the component recompute cheap.
    # unknown ids are rejected, not raised (TTL races are normal).
    st = idx.delete(np.arange(n, n + 32))  # drop half the insert above
    print(f"  delete 32 points: {st['demoted']} cores demoted, "
          f"{st['changed_grids']} grids re-decided, "
          f"{st['rejected']} ids rejected, {st['t_total'] * 1e3:.1f}ms")
    st = idx.compact()                    # re-pack tombstoned rows now
    print(f"  compact: {st['removed']} rows re-packed "
          f"({idx.n_live} live); deletes also auto-compact past "
          f"{idx.compact_threshold:.0%} dead")

    print("\ndevice-resident serving (same answers, kernel hot path):")
    # keep the serving-hot arrays resident as jax buffers: predict and
    # the delta engine's hot stages run through guard-banded float32
    # kernels, with every uncertain case re-decided by the same host
    # float64 code -- outputs stay bit-identical to host serving
    # (pinned by tests/test_device_serving.py), it is purely a faster
    # route on large batches.  drop_device_state() returns to host-only.
    idx.ensure_device_state()
    stats = {}
    labels_dev = idx.predict(queries, mode="device", stats=stats)
    assert np.array_equal(labels_dev, idx.predict(queries, mode="host"))
    print(f"  predict {len(queries)} queries on the resident state: "
          f"pack {stats['t_pack'] * 1e3:.1f}ms + kernel "
          f"{stats['t_kernel'] * 1e3:.1f}ms, {stats['uncertain']} "
          f"band-uncertain queries re-decided in float64 -- labels "
          f"bit-identical to host")
    st = idx.insert(queries[64:128])      # mutations keep buffers fresh
    print(f"  insert 64 more: donated-scatter flag updates + mirror "
          f"re-ship, {st['t_total'] * 1e3:.1f}ms")
    idx.drop_device_state()

    print("\ndistributed fit -> snapshot -> predict (the sharded plane):")
    # on a multi-device mesh pass mesh=jax.make_mesh(...) and the SPMD
    # engine fits the slabs in parallel; without one, the same serving
    # structure is built from a single-process fit
    import jax
    mesh = (jax.make_mesh((jax.device_count(),), ("shard",))
            if jax.device_count() > 1 else None)
    sidx = fit_sharded(pts, eps, min_pts, mesh=mesh, n_shards=4)
    print(f"  {sidx.num_shards} slab shards, cuts at "
          f"{np.round(sidx.cuts, 0).tolist()} (dim-0 grid lines)")
    buf = io.BytesIO()
    sidx.save(buf)                        # per-shard snapshots, one file
    buf.seek(0)
    sidx = ShardedGritIndex.load(buf)     # e.g. on the serving host
    stats = {}
    t0 = time.perf_counter()
    labels = sidx.predict(queries, stats=stats)   # slab-routed, exact
    t_pred = time.perf_counter() - t0
    print(f"  snapshot {buf.getbuffer().nbytes / 1e3:.0f}kB -> restore -> "
          f"predict {len(queries)} queries in {t_pred * 1e3:.1f}ms "
          f"({stats['multi_routed']} cut-band queries consulted both "
          f"neighbor shards)")
    st = sidx.insert(queries[:64])        # touched shards + reconcile
    print(f"  insert 64 points: shards {st['shards_touched']} touched, "
          f"{st['newly_core']} newly core, "
          f"{st['reconcile_unions']} cross-shard label unions, "
          f"{st['t_total'] * 1e3:.1f}ms")
    st = sidx.delete(np.arange(n, n + 32))  # owner + ghost copies go
    print(f"  delete 32 points: shards {st['shards_touched']} touched, "
          f"label map rebuilt from {st['reconcile_unions']} witness "
          f"unions, {st['t_total'] * 1e3:.1f}ms")
    print("done.")


if __name__ == "__main__":
    main()
