"""Rows the halo exchange selects per fit of the window, over every
shard's two boundary sides: the growth of the program's always-on
counter ``dist.halo.rows`` (``repro.obs``) over the window, from the
value ``bench/drivers/fit_sharded.py`` kept when the window began
(``counters_at_start``), over the window's fits.  None where the
program has no such counter.  Layer: halo exchange (``dist/halo.py``)."""

COUNTER = "dist.halo.rows"


def read(record):
    from repro import obs

    fits, reg = record.get("fits"), obs.registry()
    start = record.get("counters_at_start", {})
    if not fits or COUNTER not in reg.names() or COUNTER not in start:
        return None
    return (reg.counter(COUNTER).value - start[COUNTER]) / len(fits)
