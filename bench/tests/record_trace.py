#!/usr/bin/env python3
"""Record the small chip trace ``test_trace_reduce.py`` reads.

    python3 bench/tests/record_trace.py      # on a TPU, from the checkout root

Traces a few calls of the fit's Pallas core-count kernel
(``repro.kernels.ops.eps_count_batch``) under a ``bench.fit``
annotation, with host sleeps between them under ``bench.serve.idle``,
and keeps the profiler's ``.xplane.pb`` as
``bench/tests/data/v5e_small.xplane.pb`` with the window's length beside
it.
"""

import json
import shutil
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(ROOT / "src"))


def main() -> int:
    import jax
    from repro.kernels import ops

    a = jax.random.uniform(jax.random.PRNGKey(0), (8, 256, 3)) * 300
    b = jax.random.uniform(jax.random.PRNGKey(1), (8, 1024, 3)) * 300
    jax.block_until_ready(ops.eps_count_batch(a, b, 200.0))
    tdir = ROOT / ".bench_trace" / "record"
    shutil.rmtree(tdir, ignore_errors=True)
    jax.profiler.start_trace(str(tdir))
    t0 = time.perf_counter()
    for _ in range(3):
        with jax.profiler.TraceAnnotation("bench.fit"):
            jax.block_until_ready(ops.eps_count_batch(a, b, 200.0))
        with jax.profiler.TraceAnnotation("bench.serve.idle"):
            time.sleep(0.05)
    window = time.perf_counter() - t0
    jax.profiler.stop_trace()
    src = sorted(tdir.rglob("*.xplane.pb"))[-1]
    (HERE / "data").mkdir(exist_ok=True)
    size = src.stat().st_size
    shutil.copy(src, HERE / "data" / "v5e_small.xplane.pb")
    (HERE / "data" / "v5e_small.json").write_text(json.dumps(
        {"window_s": window, "device_kind": jax.devices()[0].device_kind,
         "calls": 3}) + "\n")
    shutil.rmtree(tdir, ignore_errors=True)
    print(size, window)
    return 0


if __name__ == "__main__":
    sys.exit(main())
