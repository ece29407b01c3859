"""Device busy seconds per fit: the union of the device's operation
intervals over the traced window, over the fits in it.  Layer: fit
program (``core/device_dbscan.py``)."""


def read(record):
    tr, fits = record.get("trace"), record.get("fits")
    if not tr or not fits or tr["busy_s"] <= 0:
        return None
    return tr["busy_s"] / len(fits)
