"""Benchmark harness entry point: ``python -m benchmarks.run [--quick]``.

One benchmark per paper table/figure (see paper_figs.py) plus the device
pipeline micro-benches.  Prints CSV rows (bench name + fields) and a
summary of the paper-claim checks:

  * GriT >= stencil-indexed engine (grid tree wins, Fig 11 / Figs 5-10),
  * GriT-LDF >= GriT at larger eps (union-find + low-density-first),
  * FastMerging prunes distance evals vs center/brute merging (§4.3),
  * near-linear scaling in n (Theorem 4),
  * kappa small (Remark 3: <= 11 in all paper experiments),
  * kernelized distance plane beats the naive broadcast plane on the
    largest blob scenario (the PR 2 perf-trajectory entry).

The kernel-vs-naive comparison is additionally written as JSON to
``--json-out`` (default ``BENCH_2.json``): the perf-trajectory artifact
CI uploads from every run.  ``--smoke`` runs *only* that comparison at
CI scale (seconds, not minutes).

``--serve`` runs the serving-plane benchmark instead (fitted-index
predict throughput + insert latency vs a full refit per query batch,
n = 1e5 blobs) and writes ``BENCH_3.json``; the >= 10x
predict-vs-refit check gates the run.

``--churn`` runs the mutation-plane benchmark (steady-state mixed
70/20/10 predict/insert/delete traffic against the fitted index vs a
full refit per batch, n = 1e5 blobs) and writes ``BENCH_5.json``; the
>= 10x churn-step-vs-refit check gates the run.

``--serve-device`` runs the device-resident serving benchmark
(identical mixed predict/insert/delete traffic replayed on the host
numpy path and the device-resident path, reporting the kernel-vs-
host-packing time split) and writes ``BENCH_6.json``; two checks gate
the run: device throughput >= host, and bitwise-equal outputs.

``--distributed`` runs the *sharded* serving-plane benchmark
(``ShardedGritIndex`` slab-routed predict/insert vs a distributed refit
per query batch, on a mesh over every visible device) and writes
``BENCH_4.json``; the >= 10x sharded-predict-vs-distributed-refit
check gates the run.  Under ``JAX_PLATFORMS=cpu`` it forces a 4-way
host mesh via XLA_FLAGS (set before jax is first imported, which is why
the flag must be handled before any benchmark module loads); on an
accelerator the mesh is the visible chips.  The same
invocation then writes ``BENCH_7.json`` (traced-fit stage attribution,
coverage >= 90%) and ``BENCH_8.json`` (warm distributed fit <= host
grit fit at equal total n, with the halo padding-waste <= 25% and
coverage checks riding along -- ROADMAP item 2's wall-clock gate).

``--rebalance`` runs the load-adaptive topology benchmark (rebalanced
vs static sharded serving on an adversarially skewed + drifting mixed
stream, plus R=2 replicated reads vs a single read+write index) and
writes ``BENCH_9.json``; four checks gate the run: rebalanced step
throughput >= 1.5x static, hot slab >= 4x median load, replicated
reads >= 1.8x single-index, and every read-out bit-identical to the
single-index reference.
"""

from __future__ import annotations

import argparse
import csv
import io
import json
import os
import sys


def _print_csv(rows) -> str:
    out = io.StringIO()
    fields = sorted({k for r in rows for k in r})
    w = csv.DictWriter(out, fieldnames=fields)
    w.writeheader()
    for r in rows:
        w.writerow(r)
    print(out.getvalue())
    return out.getvalue()


def _stamp(payload: dict) -> dict:
    """Provenance + metrics block shared by every BENCH_* artifact:
    ``meta`` (jax/device/git provenance -- what makes a perf row
    comparable across runs) and, when any instrument recorded,
    ``metrics`` (the process-wide registry snapshot: recompile
    counters, kernel dispatch/occupancy, halo census)."""
    from repro.obs import bench_meta, registry

    payload["meta"] = bench_meta()
    snap = registry().snapshot()
    if snap:
        payload["metrics"] = snap
    return payload


def _write_bench3(path: str, rows) -> bool:
    """Dump the serve rows + verdict as BENCH_3.json.

    Verdict: batched predict at the benched n is >= 10x faster than a
    full refit per query batch (the fitted-index acceptance bar)."""
    import jax

    pred = [r for r in rows if r.get("op") == "predict_batch"]
    verdict = bool(pred) and all(
        r["speedup_vs_refit"] >= 10.0 for r in pred)
    payload = {
        "bench": "BENCH_3",
        "backend": jax.default_backend(),
        "rows": rows,
        "checks": {"predict_10x_faster_than_refit_per_batch": verdict},
    }
    with open(path, "w") as f:
        json.dump(_stamp(payload), f, indent=2)
        f.write("\n")
    print(f"wrote {path} ({len(rows)} rows)")
    return verdict


def _write_bench5(path: str, rows) -> bool:
    """Dump the churn rows + verdict as BENCH_5.json.

    Verdict: a steady-state mixed predict/insert/delete step is >= 10x
    faster than a full refit per batch (the mutation-plane acceptance
    bar)."""
    import jax

    churn = [r for r in rows if r.get("op") == "churn_step"]
    verdict = bool(churn) and all(
        r["speedup_vs_refit"] >= 10.0 for r in churn)
    payload = {
        "bench": "BENCH_5",
        "backend": jax.default_backend(),
        "rows": rows,
        "checks": {"churn_step_10x_faster_than_refit_per_batch": verdict},
    }
    with open(path, "w") as f:
        json.dump(_stamp(payload), f, indent=2)
        f.write("\n")
    print(f"wrote {path} ({len(rows)} rows)")
    return verdict


def _write_bench6(path: str, rows) -> bool:
    """Dump the device-serving rows + verdict as BENCH_6.json.

    Verdict: the device-resident serving path matches or beats host
    throughput on identical mixed traffic, *and* its outputs (predict
    label streams + final ``labels_arrival``) are bitwise equal to the
    host run -- the device plane is only allowed to be a faster route
    to the same answer."""
    import jax

    dev = [r for r in rows if r.get("op") == "device"]
    ge_host = bool(dev) and all(r["speedup_vs_host"] >= 1.0 for r in dev)
    exact = bool(dev) and all(r["exact"] for r in dev)
    payload = {
        "bench": "BENCH_6",
        "backend": jax.default_backend(),
        "rows": rows,
        "checks": {"device_serve_ge_host_throughput": ge_host,
                   "device_bitwise_equal_host": exact},
    }
    with open(path, "w") as f:
        json.dump(_stamp(payload), f, indent=2)
        f.write("\n")
    print(f"wrote {path} ({len(rows)} rows)")
    return ge_host and exact


def _write_bench4(path: str, rows) -> bool:
    """Dump the distributed serve rows + verdict as BENCH_4.json.

    Verdict: slab-routed sharded predict is >= 10x faster than a full
    distributed refit per query batch (the sharded-index acceptance
    bar)."""
    import jax

    pred = [r for r in rows if r.get("op") == "predict_batch"]
    verdict = bool(pred) and all(
        r["speedup_vs_refit"] >= 10.0 for r in pred)
    payload = {
        "bench": "BENCH_4",
        "backend": jax.default_backend(),
        "devices": jax.device_count(),
        "rows": rows,
        "checks": {
            "sharded_predict_10x_faster_than_distributed_refit": verdict,
        },
    }
    with open(path, "w") as f:
        json.dump(_stamp(payload), f, indent=2)
        f.write("\n")
    print(f"wrote {path} ({len(rows)} rows)")
    return verdict


def _write_bench7(path: str, rows) -> bool:
    """Dump the traced-distributed-fit rows + verdict as BENCH_7.json.

    Verdict: the per-stage span totals of every traced fit (pack /
    transfer / halo exchange / local cluster / reconcile / unpack,
    with the recompile + padding-waste counters riding along in the
    rows and the ``metrics`` block) account for >= 90% of the
    ``dist.fit`` wall-clock -- the attribution quality bar for the
    ROADMAP item 2 (20x distributed-fit gap) investigation."""
    import jax

    traced = [r for r in rows if r.get("bench") == "traced_fit"]
    verdict = bool(traced) and all(
        r["coverage"] >= 0.9 for r in traced)
    payload = {
        "bench": "BENCH_7",
        "backend": jax.default_backend(),
        "devices": jax.device_count(),
        "rows": rows,
        "checks": {"stage_spans_cover_90pct_of_fit_wall": verdict},
    }
    with open(path, "w") as f:
        json.dump(_stamp(payload), f, indent=2)
        f.write("\n")
    print(f"wrote {path} ({len(rows)} rows)")
    return verdict


def _write_bench8(path: str, rows) -> bool:
    """Dump the dist-vs-host fit rows + verdict as BENCH_8.json.

    Verdict (ROADMAP item 2's wall-clock gate, all three together):

    * warm distributed fit <= host grit fit at equal total n on the
      forced multi-device mesh (occupancy-packed dispatch paying for
      the SPMD plane's padding + reconcile overhead);
    * traced-fit stage coverage >= 90% (the BENCH_7 attribution bar
      stays green on the same artifact);
    * ``dist.halo.padding_waste`` <= 25% (census-sized halo_cap on the
      quarter-pow2 ladder; worst boundary side vs cap)."""
    import jax

    warm = [r for r in rows if r.get("op") == "dist_fit_warm"]
    traced = [r for r in rows if r.get("op") == "dist_fit_traced"]
    wall_ok = bool(warm) and all(r["dist_over_host"] <= 1.0 for r in warm)
    cov_ok = bool(traced) and all(r["coverage"] >= 0.9 for r in traced)
    halo_ok = bool(traced) and all(
        r["halo_padding_waste"] <= 0.25 for r in traced)
    payload = {
        "bench": "BENCH_8",
        "backend": jax.default_backend(),
        "devices": jax.device_count(),
        "rows": rows,
        "checks": {
            "dist_fit_le_host_fit_at_equal_n": wall_ok,
            "stage_spans_cover_90pct_of_fit_wall": cov_ok,
            "halo_padding_waste_le_25pct": halo_ok,
        },
    }
    with open(path, "w") as f:
        json.dump(_stamp(payload), f, indent=2)
        f.write("\n")
    print(f"wrote {path} ({len(rows)} rows)")
    return wall_ok and cov_ok and halo_ok


def _write_bench9(path: str, rows) -> bool:
    """Dump the topology-rebalance + replica rows as BENCH_9.json.

    Verdict (ISSUE 10's load-adaptive topology gate, all together):

    * on the adversarially skewed + drifting mixed stream (hot slab
      >= 4x the median shard load), load-triggered split/merge
      rebalancing reaches >= 1.5x the static-topology step throughput;
    * R=2 replicated reads reach >= 1.8x the single-index read
      throughput (per-worker wall accounting);
    * every predict stream and the final ``labels_arrival`` is
      bit-identical to the static single-index reference, topology
      ops and replica replay included."""
    reb = [r for r in rows if r.get("op") == "rebalance_serving"]
    rep = [r for r in rows if r.get("op") == "replicated_reads"]
    reb_ok = bool(reb) and all(
        r["speedup_vs_static"] >= 1.5 for r in reb)
    skew_ok = bool(reb) and all(
        r["hot_over_median_load"] >= 4.0 for r in reb)
    rep_ok = bool(rep) and all(
        r["speedup_vs_single"] >= 1.8 for r in rep)
    bit_ok = (bool(reb) and bool(rep)
              and all(r["predicts_bitwise_static"]
                      and r["predicts_bitwise_rebalanced"]
                      and r["labels_bitwise_static"]
                      and r["labels_bitwise_rebalanced"] for r in reb)
              and all(r["reads_bitwise_identical"] for r in rep))
    payload = {
        "bench": "BENCH_9",
        "rows": rows,
        "checks": {
            "rebalanced_ge_1_5x_static_step_throughput": reb_ok,
            "hot_slab_ge_4x_median_load": skew_ok,
            "replicated_reads_ge_1_8x_single": rep_ok,
            "predict_and_labels_bitwise_identical": bit_ok,
        },
    }
    with open(path, "w") as f:
        json.dump(_stamp(payload), f, indent=2)
        f.write("\n")
    print(f"wrote {path} ({len(rows)} rows)")
    return reb_ok and skew_ok and rep_ok and bit_ok


def _write_bench_obs(path: str, rows, ratio: float) -> bool:
    """Dump the tracing-overhead rows + verdict as BENCH_OBS.json.

    Verdict: tracing-enabled serve throughput >= 0.9x tracing-off on
    the same stream (the obs overhead budget)."""
    import jax

    verdict = ratio >= 0.9
    payload = {
        "bench": "BENCH_OBS",
        "backend": jax.default_backend(),
        "rows": rows,
        "checks": {"tracing_on_ge_090x_tracing_off_throughput": verdict},
    }
    with open(path, "w") as f:
        json.dump(_stamp(payload), f, indent=2)
        f.write("\n")
    print(f"wrote {path} ({len(rows)} rows)")
    return verdict


def _write_bench2(path: str, rows, smoke: bool) -> bool:
    """Dump the kernel-vs-naive rows + verdict as BENCH_2.json.

    Returns the verdict: kernelized strictly faster than the naive
    broadcast on the largest-n blob scenario that ran."""
    import jax

    kv = [r for r in rows if r["bench"] == "kernel_vs_naive"]
    blobs = [r for r in kv if r["scenario"].startswith("blobs")]
    verdict = None
    if blobs:
        n_max = max(r["n"] for r in blobs)
        planes = {r["plane"]: r["seconds"] for r in blobs
                  if r["n"] == n_max}
        verdict = planes.get("kernelized", float("inf")) < planes.get(
            "naive", float("inf"))
    payload = {
        "bench": "BENCH_2",
        "smoke": smoke,
        "backend": jax.default_backend(),
        "rows": kv,
        "checks": {"kernelized_beats_naive_on_largest_blobs": verdict},
    }
    with open(path, "w") as f:
        json.dump(_stamp(payload), f, indent=2)
        f.write("\n")
    print(f"wrote {path} ({len(kv)} rows)")
    return bool(verdict)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smaller grids (CI-scale)")
    ap.add_argument("--smoke", action="store_true",
                    help="kernel-vs-naive distance-plane bench only "
                         "(CI smoke: seconds, not minutes); still "
                         "writes --json-out")
    ap.add_argument("--serve", action="store_true",
                    help="serving-plane bench only (fitted-index "
                         "predict/insert vs refit-per-batch); writes "
                         "BENCH_3.json")
    ap.add_argument("--serve-n", type=int, default=100_000,
                    help="fit-set size for --serve")
    ap.add_argument("--churn", action="store_true",
                    help="mutation-plane bench only (mixed 70/20/10 "
                         "predict/insert/delete traffic vs "
                         "refit-per-batch); writes BENCH_5.json")
    ap.add_argument("--churn-n", type=int, default=100_000,
                    help="fit-set size for --churn")
    ap.add_argument("--serve-device", action="store_true",
                    help="device-resident serving bench only (identical "
                         "mixed traffic on the host vs device path, "
                         "kernel-vs-packing split + bitwise exactness); "
                         "writes BENCH_6.json")
    ap.add_argument("--serve-device-n", type=int, default=60_000,
                    help="fit-set size for --serve-device")
    ap.add_argument("--serve-device-steps", type=int, default=8,
                    help="timed waves for --serve-device")
    ap.add_argument("--distributed", action="store_true",
                    help="sharded serving-plane bench only "
                         "(ShardedGritIndex predict/insert vs a "
                         "distributed refit per batch, multi-device "
                         "mesh); writes BENCH_4.json")
    ap.add_argument("--dist-n", type=int, default=50_000,
                    help="fit-set size for --distributed")
    ap.add_argument("--dist-shards", type=int, default=4,
                    help="host devices to force for --distributed "
                         "under JAX_PLATFORMS=cpu")
    ap.add_argument("--trace-n", type=int, default=None,
                    help="fit-set size for the traced-fit attribution "
                         "half of --distributed (default: --dist-n)")
    ap.add_argument("--rebalance", action="store_true",
                    help="load-adaptive topology benchmark: rebalanced "
                         "vs static sharded serving on a skewed + "
                         "drifting stream, plus R=2 replicated reads; "
                         "writes BENCH_9.json")
    ap.add_argument("--obs-overhead", action="store_true",
                    help="tracing-overhead gate only (serve throughput "
                         "with tracing on vs off, BENCH_3-shaped "
                         "stream); writes BENCH_OBS.json")
    ap.add_argument("--obs-overhead-n", type=int, default=20_000,
                    help="fit-set size for --obs-overhead")
    ap.add_argument("--out", default=None)
    ap.add_argument("--json-out", default=None,
                    help="where to write the JSON artifact (default "
                         "BENCH_2.json, BENCH_3.json under --serve, or "
                         "BENCH_4.json under --distributed)")
    args = ap.parse_args()
    if args.json_out is None:
        args.json_out = ("BENCH_4.json" if args.distributed
                         else "BENCH_9.json" if args.rebalance
                         else "BENCH_5.json" if args.churn
                         else "BENCH_6.json" if args.serve_device
                         else "BENCH_3.json" if args.serve
                         else "BENCH_2.json")

    if args.distributed and \
            os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        # must run before anything imports jax: device-count flags are
        # read at first import.  Only on a CPU-only run: on an
        # accelerator the mesh is the chips, never forced host devices
        if "xla_force_host_platform_device_count" not in \
                os.environ.get("XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "") +
                f" --xla_force_host_platform_device_count="
                f"{args.dist_shards}").strip()
        assert "jax" not in sys.modules, \
            "--distributed must configure XLA before jax is imported"
    from repro import compile_cache
    compile_cache.enable()

    if args.distributed:
        from benchmarks import dist_bench as DS
        rows = DS.bench_dist_serve(n=args.dist_n)
        csv_text = _print_csv(rows)
        if args.out:
            with open(args.out, "w") as f:
                f.write(csv_text)
        ok = _write_bench4(args.json_out, rows)
        print(f"[{'PASS' if ok else 'FAIL'}] sharded predict >= 10x "
              f"faster than a distributed refit per query batch "
              f"(n={args.dist_n})")
        # traced-fit attribution (BENCH_7): same mesh, obs tracing on
        trows = DS.bench_traced_fit(n=args.trace_n or args.dist_n)
        _print_csv(trows)
        ok7 = _write_bench7("BENCH_7.json", trows)
        print(f"[{'PASS' if ok7 else 'FAIL'}] traced fit stage spans "
              f"cover >= 90% of the dist.fit wall-clock")
        # dist-vs-host wall-clock gate (BENCH_8): same mesh, equal n
        vrows = DS.bench_dist_vs_host(n=args.dist_n)
        _print_csv(vrows)
        ok8 = _write_bench8("BENCH_8.json", vrows)
        print(f"[{'PASS' if ok8 else 'FAIL'}] warm distributed fit <= "
              f"host grit fit at n={args.dist_n} "
              f"({args.dist_shards}-way mesh), coverage >= 90%, halo "
              f"padding waste <= 25%")
        return 0 if (ok and ok7 and ok8) else 1

    if args.rebalance:
        # host-side plane (numpy index + policy): no mesh flags needed
        from benchmarks import rebalance_bench as RB
        rows = RB.bench_rebalance()
        csv_text = _print_csv(rows)
        if args.out:
            with open(args.out, "w") as f:
                f.write(csv_text)
        ok = _write_bench9(args.json_out, rows)
        print(f"[{'PASS' if ok else 'FAIL'}] rebalanced serving >= "
              f"1.5x static topology on the skewed drifting stream, "
              f"R=2 replicated reads >= 1.8x single-index, all "
              f"read-outs bit-identical")
        return 0 if ok else 1

    if args.obs_overhead:
        from benchmarks import obs_bench as OB
        rows, ratio = OB.bench_obs_overhead(n=args.obs_overhead_n)
        _print_csv(rows)
        ok = _write_bench_obs(
            args.json_out if args.json_out != "BENCH_2.json"
            else "BENCH_OBS.json", rows, ratio)
        print(f"[{'PASS' if ok else 'FAIL'}] tracing-enabled serve "
              f"throughput >= 0.9x tracing-off (ratio {ratio:.3f})")
        return 0 if ok else 1

    if args.serve_device:
        from benchmarks import serve_device_bench as SD
        rows = SD.bench_serve_device(n=args.serve_device_n,
                                     steps=args.serve_device_steps)
        csv_text = _print_csv(rows)
        if args.out:
            with open(args.out, "w") as f:
                f.write(csv_text)
        ok = _write_bench6(args.json_out, rows)
        print(f"[{'PASS' if ok else 'FAIL'}] device-resident serving "
              f">= host throughput and bitwise-equal outputs "
              f"(n={args.serve_device_n})")
        return 0 if ok else 1

    if args.churn:
        from benchmarks import churn_bench as C
        rows = C.bench_churn(n=args.churn_n)
        csv_text = _print_csv(rows)
        if args.out:
            with open(args.out, "w") as f:
                f.write(csv_text)
        ok = _write_bench5(args.json_out, rows)
        print(f"[{'PASS' if ok else 'FAIL'}] steady-state churn step "
              f">= 10x faster than a full refit per batch "
              f"(n={args.churn_n})")
        return 0 if ok else 1

    from benchmarks import paper_figs as F
    from benchmarks import device_bench as D

    if args.serve:
        from benchmarks import serve_bench as S
        rows = S.bench_serve(n=args.serve_n)
        csv_text = _print_csv(rows)
        if args.out:
            with open(args.out, "w") as f:
                f.write(csv_text)
        ok = _write_bench3(args.json_out, rows)
        print(f"[{'PASS' if ok else 'FAIL'}] batched predict >= 10x "
              f"faster than a full refit per query batch (n="
              f"{args.serve_n})")
        return 0 if ok else 1

    if args.smoke:
        # same MinPts operating point as the full bench so smoke rows
        # are comparable entries in the perf trajectory
        rows = D.bench_distance_plane(ns=(2000, 10_000),
                                      scenarios=("blobs-2d",),
                                      min_pts=64, reps=2)
        _print_csv(rows)
        ok = _write_bench2(args.json_out, rows, smoke=True)
        # informational at smoke scale: CI-sized runs sit within
        # scheduler noise of each other, so the verdict gates only the
        # full/nightly benchmark (larger n, stable margins) -- the
        # smoke job's job is producing the artifact, not timing
        print(f"[{'PASS' if ok else 'INFO'}] kernelized plane beats "
              f"naive broadcast (largest blob run; non-gating at "
              f"smoke scale)")
        return 0

    n = 3000 if args.quick else 8000
    n_tree = 6000 if args.quick else 20000
    rows = []
    rows += F.fig_runtime_vs_eps(n=n, dims=(2, 3) if args.quick
                                 else (2, 3, 5, 7))
    rows += F.fig_runtime_vs_minpts(n=n)
    rows += F.fig_runtime_vs_n(n_grid=(1000, 2000, 4000) if args.quick
                               else (2000, 4000, 8000, 16000))
    rows += F.fig_grid_tree_vs_stencil(n=n_tree,
                                       dims=(2, 3) if args.quick
                                       else (2, 3, 5, 7))
    rows += F.bench_kappa(n=n, dims=(2, 3) if args.quick else (2, 3, 5, 7))
    rows += F.bench_merge_pruning(n=n)
    # cross-engine matrix over the shared scenario catalogue (same data
    # generation as tests/test_conformance.py); the device engine joins
    # in full runs (its CPU cost is jit compiles, not clustering)
    rows += F.bench_engine_scenarios(
        engines=("brute", "grit", "grit-ldf") if args.quick
        else ("brute", "grit", "grit-ldf", "device"))
    rows += D.bench_device_dbscan(n=1024 if args.quick else 2048)
    rows += D.bench_pairwise_kernels()
    rows += D.bench_distance_plane(
        ns=(10_000,) if args.quick else (10_000, 100_000))
    rows += D.bench_lm_step()

    # ---- CSV dump ----
    csv_text = _print_csv(rows)
    if args.out:
        with open(args.out, "w") as f:
            f.write(csv_text)

    # ---- paper-claim checks ----
    ok = True

    def check(name, cond):
        nonlocal ok
        print(f"[{'PASS' if cond else 'FAIL'}] {name}")
        ok &= bool(cond)

    # Paper Fig 11 compares on PAM4D/Farm/House (d = 4, 5, 7); at d = 2
    # the stencil is a trivial 5x5 and both engines are at ms noise
    # scale, so the query-level claim is checked at d >= 3.
    tree = [r for r in rows if r["bench"] == "fig11_tree_vs_stencil"
            and r["d"] >= 3]
    check("grid tree faster than stencil at d>=3 (Fig 11)",
          all(r["tree_query_s"] <= r["stencil_query_s"] for r in tree))

    # The stencil engine's candidate set is (2*ceil(sqrt(d))+1)^d -- the
    # paper's win grows with d; at d<=3 both engines are sub-millisecond
    # and the comparison is noise, so the pipeline-level claim is checked
    # at d >= 5 (Fig 11 covers the query-level claim at every d).
    eps_rows = [r for r in rows if r["bench"] == "fig5_runtime_vs_eps"
                and r["d"] >= 5]
    by = {}
    for r in eps_rows:
        by.setdefault((r["d"], r["eps"]), {})[r["engine"]] = r["seconds"]
    grit_vs_stencil = [v["grit"] <= v["stencil"] * 1.15 for v in by.values()
                       if "grit" in v and "stencil" in v]
    if grit_vs_stencil:
        check("GriT <= stencil-indexed runtime at d>=5 (Figs 5-8)",
              sum(grit_vs_stencil) >= 0.8 * len(grit_vs_stencil))

    merge = {r["engine"]: r for r in rows
             if r["bench"] == "merge_pruning"}
    check("FastMerging prunes distance evals vs brute merging (§4.3)",
          merge["fast"]["dist_evals"] < 0.5 * merge["brute"]["dist_evals"])

    scal = [r for r in rows if r["bench"] == "fig7_runtime_vs_n"
            and r["engine"] == "grit"]
    if len(scal) >= 2:
        per_k = [r["sec_per_kpoint"] for r in sorted(scal,
                                                     key=lambda r: r["n"])]
        check("near-linear scaling in n (Theorem 4): sec/kpoint drift < 3x",
              per_k[-1] <= 3.0 * max(per_k[0], 1e-9))

    kap = [r for r in rows if r["bench"] == "kappa"]
    check("kappa <= 11 (Remark 3)", all(r["kappa_max"] <= 11 for r in kap))

    # kernelized vs naive distance plane (PR 2 tentpole): the kernel
    # route must beat the naive broadcast on the largest blob scenario,
    # and both planes must report identical cluster/noise counts
    ok_kernel = _write_bench2(args.json_out, rows, smoke=False)
    check("kernelized plane beats naive broadcast (largest blob run)",
          ok_kernel)
    # the two planes sum d2 in different orders (direct vs aa+bb-2ab on
    # re-centered coords), and the rescaled bench parameters carry none
    # of the catalogue's engineered decision margins -- so a knife-edge
    # point may legitimately flip by 1 ulp.  Cluster counts must match
    # exactly; noise counts get a 0.2% tolerance for such flips.
    kv = {}
    for r in rows:
        if r["bench"] == "kernel_vs_naive":
            kv.setdefault((r["scenario"], r["n"]), {})[r["plane"]] = r
    check("distance planes agree on cluster/noise counts",
          bool(kv) and all(
              v["naive"]["clusters"] == v["kernelized"]["clusters"]
              and abs(v["naive"]["noise"] - v["kernelized"]["noise"])
              <= max(1, int(0.002 * v["naive"]["n"]))
              for v in kv.values()))

    # every engine must report identical cluster/noise counts on every
    # scenario (Theorem 4 exactness; label-level equivalence is enforced
    # by tests/test_conformance.py)
    scen = {}
    for r in rows:
        if r["bench"] == "engine_scenarios":
            scen.setdefault(r["scenario"], set()).add(
                (r["clusters"], r["noise"]))
    check("engines agree on the scenario matrix (Theorem 4)",
          bool(scen) and all(len(v) == 1 for v in scen.values()))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
