"""Share (%) of the core-counting kernel's roofline: the least time the
chip needs for the distances exact grid DBSCAN's core counting requires
(``bench/roofline.py``, counted on the host in set-up), over the
``eps_count_batch`` kernel's device time per fit.  The bound (compute
or memory) is whichever of the two times is larger."""

from bench import roofline, trace_reduce

PATTERN = r"eps_count_batch"


def read(record):
    tr, fits = record.get("trace"), record.get("fits")
    work = record.get("roofline", {}).get("eps_count")
    if not tr or not fits or not work:
        return None
    s = trace_reduce.op_seconds(tr, PATTERN) / len(fits)
    if s <= 0:
        return None
    t, _ = roofline.roofline_s(work, record["peaks"])
    return 100.0 * t / s
