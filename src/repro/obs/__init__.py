"""``repro.obs``: the tracing + metrics plane.

One instrumentation layer for every subsystem that used to log in its
own dict schema -- spans (``repro.obs.trace``), a process-wide metric
registry (``repro.obs.metrics``), Chrome-trace / JSONL exporters
(``repro.obs.export``) and a text summarizer
(``python -m repro.obs.view``).  DESIGN.md §9 has the span taxonomy
and the overhead policy; the short version:

* tracing **off** (default): ``obs.span(...)`` returns a shared no-op
  -- zero events, zero host syncs, the serving hot path is untouched;
* profiler recording (``jax.profiler.start_trace``): every span also
  writes a ``jax.profiler.TraceAnnotation`` of its name into the
  profiler's trace -- and, with tracing off, nothing else (no sync);
* tracing **on** (``REPRO_OBS=1`` or :func:`enable`): spans sync at
  close only, counters/histograms always record (they are host-side
  integer adds and never sync);
* :func:`stage` is a span plus an always-on ``<name>_s`` histogram of
  its seconds, for the stages a benchmark reads without a trace.

Environment switches (read once at import):

* ``REPRO_OBS=1`` -- enable tracing and the ``jax.monitoring`` bridge.
* ``REPRO_OBS_TRACE=<path>`` -- at process exit, export the Chrome
  trace (with the metrics snapshot and ``bench_meta`` provenance)
  there; implies ``REPRO_OBS=1``.
"""

from __future__ import annotations

import atexit
import contextlib
import os
import time
from typing import Optional

from . import export
from .meta import bench_meta, git_rev
from .metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                      counter, gauge, histogram, install_jax_hooks,
                      jax_hooks_installed, recompile_counts, registry)
from .trace import (NOOP_SPAN, Span, Tracer, disable, enable, enabled,
                    get_tracer, span)

__all__ = [
    "span", "enabled", "enable", "disable", "get_tracer", "Tracer",
    "Span", "NOOP_SPAN",
    "MetricsRegistry", "registry", "counter", "gauge", "histogram",
    "Counter", "Gauge", "Histogram",
    "install_jax_hooks", "jax_hooks_installed", "recompile_counts",
    "bench_meta", "git_rev", "export",
    "note_flat_dispatch", "export_chrome", "stage",
]


@contextlib.contextmanager
def stage(name: str, **attrs):
    """One stage of a call: ``span(name, **attrs)`` around the block,
    and the block's seconds observed into the default registry's
    histogram ``<name>_s`` -- always, like every registry instrument
    (two ``perf_counter`` reads and one ``observe``), so the two
    cannot drift apart.  A block that raises is not observed."""
    t0 = time.perf_counter()
    with span(name, **attrs) as sp:
        yield sp
    histogram(f"{name}_s").observe(time.perf_counter() - t0)


def note_flat_dispatch(stage: str, t_valid: int, bucket: int) -> None:
    """Record one flat ragged kernel dispatch (``pairwise_d2_flat`` /
    ``_flat_res``): dispatch count, valid elements, and the pow2 bucket
    elements actually shipped -- ``elems / bucket_elems`` is the bucket
    occupancy (1 - padding waste).  Host-side counter adds only: safe
    on the serving hot path."""
    r = registry()
    r.counter(f"kernels.flat.{stage}.dispatches").inc()
    r.counter(f"kernels.flat.{stage}.elems").inc(t_valid)
    r.counter(f"kernels.flat.{stage}.bucket_elems").inc(bucket)


def export_chrome(path: str, reg: Optional[MetricsRegistry] = None,
                  meta: bool = True) -> bool:
    """Export the live tracer's events as a Chrome trace at ``path``
    (with the registry snapshot + provenance).  Returns False when
    tracing was never enabled (nothing to export)."""
    t = get_tracer()
    if t is None:
        return False
    export.write_chrome_trace(
        path, t.snapshot_events(),
        metrics=(reg or registry()).snapshot(),
        meta=bench_meta() if meta else None)
    return True


def _env_truthy(name: str) -> bool:
    return os.environ.get(name, "").strip().lower() not in (
        "", "0", "false", "no", "off")


_TRACE_OUT = os.environ.get("REPRO_OBS_TRACE", "").strip()
if _env_truthy("REPRO_OBS") or _TRACE_OUT:
    enable()
    install_jax_hooks()
    if _TRACE_OUT:
        atexit.register(export_chrome, _TRACE_OUT)
