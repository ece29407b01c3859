"""Host seconds per fit of ``cluster()``'s device engine outside the
census and the device program: the float32 cast, range check, padding
and upload before it (``engine.device.prepare``), and the fetch of
labels and core flags and the result's build after it
(``engine.device.fetch``).  Read in process from the program's
always-on stage histograms ``engine.device.prepare_s`` and
``engine.device.fetch_s`` (``repro.obs``): the sum of the means of
their last ``len(fits)`` observations, which are the window's fits.
None where the program has no such histograms.  Layer: entry
(``engine/engines.py``)."""

HISTOGRAMS = ("engine.device.prepare_s", "engine.device.fetch_s")


def read(record):
    from repro import obs

    fits, reg = record.get("fits"), obs.registry()
    if not fits or not set(HISTOGRAMS) <= set(reg.names()):
        return None
    total = 0.0
    for h in HISTOGRAMS:
        vals = reg.histogram(h).values()
        if len(vals) < len(fits):
            return None
        total += sum(vals[-len(fits):]) / len(fits)
    return total
