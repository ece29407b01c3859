"""Device seconds per fit of the SPMD step's collectives: the halo and
label exchanges' ``collective-permute``, the reconciliation's
``all-gather`` and any ``all-reduce``, plain or as asynchronous
``-start`` / ``-done`` pairs, from the trace's per-operation time
summed over the devices, divided by the devices seen.  Layer: SPMD
step (``dist/step.py``)."""

import re

PATTERN = re.compile(r"%?(collective-permute|all-gather|all-reduce)\b")


def read(record):
    tr, fits = record.get("trace"), record.get("fits")
    if not tr or not fits or not tr.get("devices_seen"):
        return None
    s = sum(v for k, v in tr["op_s"].items() if PATTERN.match(k))
    return s / tr["devices_seen"] / len(fits) if s > 0 else None
