"""Host seconds per fit of the distributed fit's own host stages: the
slab cuts and packing (``dist.fit.pack``), the upload of the packed
slabs to the mesh (``dist.fit.transfer``), and the labels' and core
flags' return to the original row order (``dist.fit.unpack``).  Read in
process from the program's always-on stage histograms
``dist.fit.pack_s``, ``dist.fit.transfer_s`` and ``dist.fit.unpack_s``
(``repro.obs``): the sum of the means of their last ``len(fits)``
observations, which are the window's fits.  None where the program has
no such histograms.  Layer: entry (``dist/api.py``)."""

HISTOGRAMS = ("dist.fit.pack_s", "dist.fit.transfer_s",
              "dist.fit.unpack_s")


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
