#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 bench/main.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell is made of is found by name, so a new cell needs new
files and entries and no edit of this one:

* ``BENCHMARK.json`` (root of the checkout): the cell's ``config``,
  ``traffic`` and ``chips``, and the metrics it reports;
* ``bench/configs/<config>.json``: the deployment (table, eps, MinPts,
  the guarantees it states);
* ``bench/traffic/<traffic>.json``: the mix; its ``driver`` names the
  module ``bench/drivers/<driver>.py`` that sets up, warms and drives
  the window (``setup(ctx)``, ``window(ctx)``, ``check(ctx)``);
* ``bench/metrics/<metric>.py``: one reader per per-layer metric,
  ``read(record) -> float | None``, over the traced run's record.

A run: load the cell, make the table and traffic from ``--seed``, set
up and warm only the cell's shapes (``setup_s``), measure for
``--seconds``, read ``memory_peak_bytes``, free the program's state,
compare what the window produced with the plain reference
(``bench/reference.py``), and print one JSON line.  With ``--trace 1``
the window runs under the JAX profiler and the line carries the
per-layer metrics, ``busy_s``/``window_s`` and a ``breakdown``.

The run exits non-zero, with no result line, off an accelerator, with
fewer chips than the cell asks for, on a device kind missing from
``bench/peaks.json``, or when the program under test is not in the
checkout.

``--rehearse N`` runs every phase on whatever JAX finds (the CPU, off the chip)
with the table cut to N rows, prints what it would report to stderr,
prints no result line and exits 3: a check of the control flow, never a
measurement.
"""

from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import sys
import time
from pathlib import Path

T_START = time.perf_counter()
BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(ROOT))


def _load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = BENCH / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(workload: str):
    """The cell's entry, configuration, traffic and metrics."""
    spec = _load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; have "
                         f"{sorted(cells)}")
    cell = cells[workload]
    config = _load_json(BENCH / "configs" / f"{cell['config']}.json")
    traffic = _load_json(BENCH / "traffic" / f"{cell['traffic']}.json")

    def applies(m):
        return "workloads" not in m or workload in m["workloads"]

    e2e = [m for m in spec["end_to_end"] if applies(m)]
    layer = [m for m in spec["per_layer"] if applies(m)]
    return cell, config, traffic, e2e, layer


def device_report(chips: int, rehearse: bool = False):
    """The devices as JAX reports them; exits non-zero off an
    accelerator, with too few chips, or on a kind with no peaks."""
    import jax
    devs = jax.devices()
    kind = devs[0].device_kind
    if rehearse:
        return devs[:chips], _load_json(BENCH / "peaks.json")["devices"][
            "TPU v5 lite"]
    if devs[0].platform == "cpu":
        raise SystemExit("no accelerator: JAX found only the CPU")
    if len(devs) < chips:
        raise SystemExit(f"the cell asks for {chips} chips, JAX found "
                         f"{len(devs)}")
    peaks = _load_json(BENCH / "peaks.json")["devices"]
    if kind not in peaks:
        raise SystemExit(f"no peaks for device kind {kind!r} in "
                         f"bench/peaks.json")
    return devs[:chips], peaks[kind]


def enable_compile_cache() -> None:
    """JAX's persistent compilation cache inside the checkout (or where
    ``JAX_COMPILATION_CACHE_DIR`` says), through the program's own
    switch, which also makes source paths relative to the checkout so
    that another checkout finds the same entries.  Every program is
    cached, the short ones too."""
    import jax
    from repro import compile_cache
    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def compile_counts():
    """(backend compiles, persistent-cache hits) so far."""
    from repro import obs
    snap = obs.registry().snapshot()
    dur = snap.get("jax.dur.jax.core.compile.backend_compile_duration",
                   {"count": 0})
    return (int(dur["count"]),
            int(snap.get("jax.events.jax.compilation_cache.cache_hits", 0)))


class Ctx:
    """What a driver gets: the cell's files, the seed, the devices, and
    a ``record`` dict it fills for the metric readers."""

    def __init__(self, args, cell, config, traffic, devices, peaks):
        self.seed = args.seed
        self.seconds = float(args.seconds)
        self.trace = bool(args.trace)
        self.cell = cell
        self.config = config
        self.traffic = traffic
        self.devices = devices
        self.peaks = peaks
        self.record = {"cell": cell["name"], "peaks": peaks}
        self.state = {}


def run_window(ctx, driver):
    """The measured window, under the profiler when tracing."""
    if not ctx.trace:
        return driver.window(ctx)
    import jax
    from bench import trace_reduce
    tdir = ROOT / ".bench_trace" / ctx.cell["name"]
    trace_reduce.clear(tdir)
    jax.profiler.start_trace(str(tdir))
    try:
        out = driver.window(ctx)
    finally:
        t0 = time.perf_counter()
        jax.profiler.stop_trace()
    t1 = time.perf_counter()
    tr = ctx.record["trace"] = trace_reduce.reduce_dir(
        tdir, ctx.record["window_s"], len(ctx.devices))
    print(f"[trace] bytes={tr['trace_bytes']} stop_s={t1 - t0} "
          f"reduce_s={time.perf_counter() - t1} "
          f"custom_calls={json.dumps({k: v[:200] for k, v in tr['custom_calls'].items()})} "
          f"inventory={json.dumps(tr['inventory'])}",
          file=sys.stderr, flush=True)
    trace_reduce.clear(tdir)
    return out


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", type=int, default=0, metavar="N",
                    help="control-flow check off the chip at N rows; "
                         "prints no result line and exits 3")
    return ap.parse_args(argv)


def run(args, t_start: float) -> dict:
    """One run of one cell; returns the result line as a dict."""
    cell, config, traffic, e2e, layer = load_cell(args.workload)
    if args.rehearse:
        config["data"]["n"] = args.rehearse
    if not (ROOT / "src" / "repro").is_dir():
        raise SystemExit("the program under test (src/repro) is not in "
                         "this checkout")
    sys.path.insert(0, str(ROOT / "src"))
    enable_compile_cache()
    from repro import obs
    obs.install_jax_hooks()
    devices, peaks = device_report(int(cell["chips"]), bool(args.rehearse))
    driver = load_module("drivers", traffic["driver"])
    ctx = Ctx(args, cell, config, traffic, devices, peaks)

    driver.setup(ctx)
    setup_s = time.perf_counter() - t_start
    c0 = compile_counts()
    measured = run_window(ctx, driver)
    c1 = compile_counts()
    if c1 != c0:
        raise SystemExit(
            f"the window compiled {c1[0] - c0[0]} programs and loaded "
            f"{c1[1] - c0[1]} from the cache: set-up did not warm every "
            f"shape the window uses")
    stats = devices[0].memory_stats() or {}
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)
    gc.collect()

    checks = driver.check(ctx)          # [(name, value, limit), ...]
    correct = all(v <= lim for _, v, lim in checks)
    record = ctx.record
    measured["setup_s"] = setup_s
    if args.trace:
        values = {}
        for m in layer:
            v = load_module("metrics", m["name"]).read(record)
            if v is not None:
                values[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        values = {m["name"]: {"value": float(measured[m["name"]]),
                              "unit": m["unit"]}
                  for m in e2e if m["name"] in measured}
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    line = {"correct": bool(correct),
            "attempted": int(measured["attempted"]),
            "failed": int(measured["failed"]),
            "metrics": values, "device": device}
    if args.trace:
        tr = record["trace"]
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        line["breakdown"] = {"device_ops": tr["device_ops"],
                             "idle_gaps": tr["idle_gaps"]}
    print(f"[run] workload={args.workload} seed={args.seed} "
          f"setup_s={setup_s} run_s={time.perf_counter() - t_start} "
          f"bytes_in_use_after={stats.get('bytes_in_use')}",
          file=sys.stderr, flush=True)
    for name, v, lim in checks:
        print(f"[check] {name}: {v} (limit {lim})", file=sys.stderr,
              flush=True)
    line["checks"] = {name: {"value": v, "limit": lim}
                      for name, v, lim in checks}
    return line


def main(argv=None) -> int:
    args = parse(argv)
    line = run(args, T_START)
    if args.rehearse:
        print(f"[rehearsal] {json.dumps(line)}", file=sys.stderr,
              flush=True)
        return 3
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
