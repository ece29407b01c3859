"""Share (%) of the traced fit window in which the device ran no
operation: 100 * (1 - busy / window)."""


def read(record):
    tr = record.get("trace")
    if not tr or not record.get("fits") or tr["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
