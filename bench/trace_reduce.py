"""From a JAX profiler trace of the window to device busy time, kernel
time and the breakdown.

Reads the ``*.xplane.pb`` that ``jax.profiler.start_trace`` writes, with
nothing but ``jax.profiler.ProfileData``:

* device planes are ``/device:TPU:<k>``; an operation is an event of
  their ``XLA Ops`` line;
* busy: the union of the operation intervals of each device, averaged
  over the devices the cell uses; idle share = 1 - busy / window;
* operation time: summed device durations per operation, keyed by the
  HLO instruction's name (``%fusion.12``; the event's name is the whole
  instruction text, which names a Pallas kernel only inside its
  custom call, so custom calls keep the kernel's name:
  ``%custom-call.5 _eps_count_batch_kernel``); a metric reader sums the
  names of its kernel;
* breakdown: the ten operation names that took most device time, and
  the ten host spans under which the device sat idle longest -- each
  idle gap of device 0 inside the window (from the first ``bench.*``
  annotation to the end of the last) is named by the innermost event
  open at its midpoint on the thread that drives the window (the
  annotation itself, or the Python calls and runtime events inside it).
"""

from __future__ import annotations

import re
import shutil
from collections import defaultdict
from pathlib import Path

import numpy as np

DEVICE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"


class _Plane:
    """A plane with its lines and events read once into lists (the
    profiler hands them out as one-pass iterators)."""

    def __init__(self, plane):
        self.name = plane.name
        self.lines = [_Line(line) for line in plane.lines]


class _Line:
    def __init__(self, line):
        self.name = line.name
        self.events = [_Event(e) for e in line.events]


class _Event:
    __slots__ = ("name", "start_ns", "duration_ns")

    def __init__(self, e):
        self.name, self.start_ns, self.duration_ns = \
            e.name, e.start_ns, e.duration_ns


def clear(tdir) -> None:
    shutil.rmtree(tdir, ignore_errors=True)


KERNEL = re.compile(r'kernel_name\s*=\s*"?([A-Za-z0-9_.]+)|'
                    r'"name"\s*:\s*"([A-Za-z0-9_.]+)"')


def op_name(text: str) -> str:
    """Short name of an operation event: the instruction name, with the
    kernel's name appended for a custom call."""
    head = text.split(" = ", 1)[0].strip()
    if "custom-call" in head or "custom_call" in text[:200]:
        m = KERNEL.search(text)
        if m:
            return f"{head} {m.group(1) or m.group(2)}"
    return head


def _union(intervals):
    """Merged, sorted [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _host_events(planes):
    """Events of the host lines that hold the benchmark's ``bench.*``
    annotations (the thread that drives the window), or of every host
    line when none does."""
    lines = [line for plane in planes if not DEVICE.match(plane.name)
             for line in plane.lines]
    main = [line for line in lines
            if any(e.name.startswith("bench.") for e in line.events)]
    ev = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
          for line in (main or lines) for e in line.events]
    bench = [(s, e) for s, e, n in ev if n.startswith("bench.")] or \
        [(s, e) for s, e, _ in ev]
    span = (min(s for s, _ in bench), max(e for _, e in bench)) \
        if bench else (0, 0)
    return ev, span


def _open_at(host, hs, he, t: int, reach: int = 4096) -> str:
    """Name of the shortest host event open at ``t`` among the
    ``reach`` latest to start before it."""
    k = int(np.searchsorted(hs, t, side="right"))
    lo = max(0, k - reach)
    inside = np.flatnonzero(he[lo:k] > t)
    if not len(inside):
        return "(no host event)"
    j = lo + inside[np.argmin(he[lo:k][inside] - hs[lo:k][inside])]
    return host[j][2]


def inventory(planes, top: int = 5):
    """Planes, their lines, event counts and most frequent names: what
    to look at before trusting the reduction on a new platform."""
    out = []
    for plane in planes:
        lines = []
        for line in plane.lines:
            names = defaultdict(int)
            for e in line.events:
                names[e.name] += 1
            lines.append([line.name, sum(names.values()),
                          [k[:120] for k in sorted(
                              names, key=lambda k: -names[k])[:top]]])
        out.append([plane.name, lines])
    return out


def reduce_planes(planes, window_s: float, n_devices: int) -> dict:
    """The reduction of one trace (see the module docstring)."""
    busy = {}
    op_time = defaultdict(float)
    names, texts = {}, {}
    merged0 = []
    for plane in planes:
        m = DEVICE.match(plane.name)
        if not m or int(m.group(1)) >= n_devices:
            continue
        spans = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for e in line.events:
                spans.append((e.start_ns, e.start_ns + e.duration_ns))
                key = names.get(e.name)
                if key is None:
                    key = names[e.name] = op_name(e.name)
                    if "custom" in key or "custom-call" in e.name[:300]:
                        texts[key] = e.name[:4000]
                op_time[key] += e.duration_ns * 1e-9
        merged = _union(spans)
        busy[int(m.group(1))] = sum(e - s for s, e in merged) * 1e-9
        if int(m.group(1)) == 0:
            merged0 = merged
    n = max(len(busy), 1)
    host, (lo, hi) = _host_events(planes)
    gaps = defaultdict(float)
    if merged0 and host:
        host.sort()
        hs = np.array([h[0] for h in host], np.int64)
        he = np.array([h[1] for h in host], np.int64)
        edges = [lo] + [x for iv in merged0 for x in iv] + [hi]
        for s, e in zip(edges[0::2], edges[1::2]):
            s, e = max(s, lo), min(e, hi)
            if e <= s:
                continue
            gaps[_open_at(host, hs, he, (s + e) // 2)] += (e - s) * 1e-9
    top = sorted(op_time.items(), key=lambda kv: -kv[1])[:10]
    idle = sorted(gaps.items(), key=lambda kv: -kv[1])[:10]
    return {"busy_s": sum(busy.values()) / n,
            "busy_per_device_s": [busy[k] for k in sorted(busy)],
            "window_s": window_s,
            "op_s": dict(op_time),
            "custom_calls": texts,
            "device_ops": [[k, v] for k, v in top],
            "idle_gaps": [[k, v] for k, v in idle],
            "devices_seen": len(busy)}


def op_seconds(trace: dict, pattern: str) -> float:
    """Device seconds of the operations whose short name, or custom-call
    text, matches ``pattern``."""
    pat = re.compile(pattern)
    texts = trace.get("custom_calls", {})
    return sum(v for k, v in trace["op_s"].items()
               if pat.search(k) or pat.search(texts.get(k, "")))


def reduce_dir(tdir, window_s: float, n_devices: int) -> dict:
    """Reduce the one ``*.xplane.pb`` under ``tdir``."""
    from jax.profiler import ProfileData
    files = sorted(Path(tdir).rglob("*.xplane.pb"))
    if not files:
        raise RuntimeError(f"the profiler wrote no trace under {tdir}")
    planes = [_Plane(p) for p in ProfileData.from_file(str(files[-1])).planes]
    out = reduce_planes(planes, window_s, n_devices)
    out["inventory"] = inventory(planes)
    out["trace_bytes"] = files[-1].stat().st_size
    return out
