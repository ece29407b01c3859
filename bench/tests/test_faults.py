"""A whole run with the timed path broken underneath must come out with
``correct`` false: the harness's look for a chip is skipped
(``--rehearse``) and everything else runs as on the chip, at a small
table.  Faults the fit cell can have: an answer altered where it is
produced, and half of the batch left out.  (It carries no state from
fit to fit and spans no chips, so the other two faults do not apply.)"""

import time

import numpy as np
import pytest

from bench import main

N = 3000


def run(cell, seed=4, seconds=1.0):
    args = main.parse(["--workload", cell, "--seed", str(seed),
                       "--seconds", str(seconds), "--trace", "0",
                       "--rehearse", str(N)])
    return main.run(args, time.perf_counter())


def test_sound_run_is_correct():
    line = run("ss3d-1m-batch.fit")
    assert line["correct"], line["checks"]


@pytest.mark.parametrize("fault", ["answer_altered", "half_left_out"])
def test_fit_fault_is_caught(monkeypatch, fault):
    import repro.engine as engine
    real = engine.cluster

    def broken(points, eps, min_pts, **kw):
        res = real(points, eps, min_pts, **kw)
        if fault == "half_left_out":
            # the second half of the rows gets no answer
            half = len(res.labels) // 2
            res.labels = res.labels.copy()
            res.core = res.core.copy()
            res.labels[half:] = -1
            res.core[half:] = False
            return res
        i = int(np.flatnonzero(res.labels >= 0)[0])
        res.labels = res.labels.copy()
        res.labels[i] = res.labels.max() + 1
        return res

    monkeypatch.setattr(engine, "cluster", broken)
    line = run("ss3d-1m-batch.fit")
    assert not line["correct"], line["checks"]


def test_a_window_that_compiles_fails_the_run(monkeypatch):
    counts = iter([(0, 0), (1, 0)])
    monkeypatch.setattr(main, "compile_counts", lambda: next(counts))
    with pytest.raises(SystemExit, match="compiled 1 programs"):
        run("ss3d-1m-batch.fit")


def test_set_up_falls_back_to_a_whole_fit(monkeypatch, capsys):
    from repro.core import device_dbscan

    def changed(*args, **kw):
        raise TypeError("device_dbscan() got an unexpected argument")

    monkeypatch.setattr(device_dbscan, "device_dbscan", changed)
    line = run("ss3d-1m-batch.fit")
    assert line["correct"], line["checks"]
    assert "warming by a whole fit" in capsys.readouterr().err
