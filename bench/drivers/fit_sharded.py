"""Sharded batch fit: cluster the whole table back to back on a 1-D
mesh over the cell's chips, as a user of a four-chip host calls it.

Set-up makes the table, counts the core-counting work for the roofline
(``bench/roofline.py``) and warms by one whole
``cluster(engine="distributed", mesh=...)`` call on the mesh the window
uses, built from the harness's devices.  That call sizes the caps by
the program's own per-shard census and compiles the SPMD step (or loads
it from the persistent cache) with exactly the caps the window's fits
size again, so the window compiles nothing; the harness fails the run
if it does.

The window runs ``repro.engine.cluster`` on the table until
``--seconds`` have passed; the fit in flight then is finished and
counted.  ``fit_s`` is the window's whole time over the fits it holds.
Records and check are ``bench/drivers/fit.py``'s (every fit against
the plain reference); the record also keeps the program's counters as
they stood when the window began, for readers of per-fit counts.
"""

from __future__ import annotations

import time

import numpy as np

from bench import gen, roofline
from bench.drivers.fit import check  # noqa: F401  (the harness calls it)


def _mesh(ctx):
    from jax.sharding import Mesh

    shards = int(ctx.config["shards"])
    if len(ctx.devices) != shards:
        raise SystemExit(f"the configuration shards over {shards} "
                         f"devices, the run has {len(ctx.devices)}")
    return Mesh(np.array(ctx.devices), ("shard",))


def _fit(ctx):
    from repro.engine import cluster

    cfg = ctx.config
    return cluster(ctx.state["table"], cfg["eps"], cfg["min_pts"],
                   engine=cfg["engine"], mesh=ctx.state["mesh"])


def setup(ctx) -> None:
    cfg = ctx.config
    table = gen.make_table(cfg, ctx.seed)
    ctx.state["table"] = table
    ctx.state["mesh"] = _mesh(ctx)
    ctx.record["roofline"] = {"eps_count": roofline.eps_count_work(
        table, cfg["eps"], cfg["min_pts"])}
    _fit(ctx)


def window(ctx) -> dict:
    import jax
    from repro import obs

    ctx.record["counters_at_start"] = {
        k: v for k, v in obs.registry().snapshot().items()
        if not isinstance(v, dict)}
    fits = []
    t0 = time.perf_counter()
    while True:
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.fit"):
            res = _fit(ctx)
        fits.append({"s": time.perf_counter() - t,
                     "attempts": len(res.attempts),
                     "labels": res.labels, "core": res.core})
        if time.perf_counter() - t0 >= ctx.seconds:
            break
    elapsed = time.perf_counter() - t0
    ctx.state["fits"] = fits
    ctx.record["fits"] = [{k: f[k] for k in ("s", "attempts")}
                          for f in fits]
    ctx.record["window_s"] = elapsed
    return {"fit_s": elapsed / len(fits), "attempted": len(fits),
            "failed": 0}
