"""Adaptive-cap attempts per fit of the window (``len(res.attempts)``);
1 when the census-sized caps hold.  Layer: adaptive caps
(``engine/adaptive.py``)."""


def read(record):
    fits = record.get("fits")
    if not fits:
        return None
    return sum(f["attempts"] for f in fits) / len(fits)
