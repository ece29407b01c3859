"""Device seconds per fit of the Pallas pairwise kernels of the fit
(``eps_count_batch`` and ``row_min_batch``, ``kernels/pairwise.py``),
from the trace."""

from bench import trace_reduce

PATTERN = r"eps_count_batch|row_min_batch"


def read(record):
    tr, fits = record.get("trace"), record.get("fits")
    if not tr or not fits:
        return None
    s = trace_reduce.op_seconds(tr, PATTERN)
    return s / len(fits) if s > 0 else None
