"""Host seconds per fit of ``cluster()``'s grid census, the pass that
sizes the fit program's caps (``estimate_caps`` / ``stencil_census``)
while the device waits.  Read in process from the program's always-on
stage histogram ``engine.census_s`` (``repro.obs``): the mean of its
last ``len(fits)`` observations, which are the window's fits (set-up
calls ``estimate_caps`` directly, and nothing after the window calls
``cluster()``).  None where the program has no such histogram.  Layer:
adaptive caps (``engine/adaptive.py``)."""

HISTOGRAMS = ("engine.census_s",)


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
