"""The readers of the program's stage histograms: ``fit.host_census_s``
and ``fit.host_other_s`` average the window's fits, the last
``len(fits)`` observations, and find nothing where the program has no
such histogram."""

import pytest

from bench import main
from repro import obs
from repro.obs.metrics import MetricsRegistry


@pytest.fixture
def reg(monkeypatch):
    r = MetricsRegistry()
    monkeypatch.setattr(obs, "registry", lambda: r)
    return r


def _read(metric, fits):
    return main.load_module("metrics", metric).read(
        {"fits": [{"s": 1.0, "attempts": 1}] * fits})


def test_census_reads_the_mean_of_the_window_fits(reg):
    for v in (40.0, 41.0, 43.0):
        reg.histogram("engine.census_s").observe(v)
    assert _read("fit.host_census_s", 2) == pytest.approx(42.0)
    assert _read("fit.host_census_s", 3) == pytest.approx(124.0 / 3)


def test_other_sums_prepare_and_fetch_over_the_window_fits(reg):
    for p, f in ((9.0, 9.0), (0.25, 1.0), (0.75, 2.0)):
        reg.histogram("engine.device.prepare_s").observe(p)
        reg.histogram("engine.device.fetch_s").observe(f)
    assert _read("fit.host_other_s", 2) == pytest.approx(0.5 + 1.5)


@pytest.mark.parametrize("metric,present", [
    ("fit.host_census_s", ()),
    ("fit.host_other_s", ("engine.device.prepare_s",)),
])
def test_a_missing_histogram_reads_none(reg, metric, present):
    for h in present:
        reg.histogram(h).observe(1.0)
    assert _read(metric, 1) is None
    assert reg.names() == sorted(present)     # the reader made none


def test_fewer_observations_than_fits_read_none(reg):
    reg.histogram("engine.census_s").observe(40.0)
    assert _read("fit.host_census_s", 2) is None
