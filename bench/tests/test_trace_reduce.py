"""The trace reduction on a small trace: busy union, idle share,
operation time, breakdown."""

from types import SimpleNamespace as NS

from bench import trace_reduce


def ev(name, start_us, dur_us):
    return NS(name=name, start_ns=int(start_us * 1000),
              duration_ns=int(dur_us * 1000))


def planes():
    ops = [ev("fusion.1", 0, 100), ev("custom-call.eps_count_batch", 50, 100),
           ev("fusion.1", 400, 100), ev("while.2", 900, 50)]
    host = [ev("bench.fit", 0, 1000), ev("np.unique", 200, 150),
            ev("bench.serve.idle", 600, 250)]
    return [NS(name="/device:TPU:0",
               lines=[NS(name="XLA Modules", events=[ev("jit_f", 0, 950)]),
                      NS(name="XLA Ops", events=ops)]),
            NS(name="/device:TPU:1",
               lines=[NS(name="XLA Ops", events=[ev("fusion.9", 0, 500)])]),
            NS(name="/host:CPU",
               lines=[NS(name="python", events=host),
                      NS(name="client", events=[ev("sleep", 0, 1000),
                                                ev("submit", 700, 10)])])]


def test_busy_union_idle_and_breakdown():
    out = trace_reduce.reduce_planes(planes(), window_s=1e-3, n_devices=1)
    # 0-150 (overlap merged), 400-500, 900-950
    assert abs(out["busy_s"] - 300e-6) < 1e-12
    assert out["devices_seen"] == 1
    assert out["device_ops"][0] == ["fusion.1", 200e-6]
    assert abs(trace_reduce.op_seconds(out, "eps_count") - 100e-6) < 1e-12
    gaps = dict(out["idle_gaps"])
    # 150-400 under np.unique, 500-900 under bench.serve.idle, 950-1000
    # under bench.fit
    assert abs(gaps["np.unique"] - 250e-6) < 1e-12
    assert abs(gaps["bench.serve.idle"] - 400e-6) < 1e-12
    assert abs(gaps["bench.fit"] - 50e-6) < 1e-12


def test_busy_averages_over_the_cells_devices():
    out = trace_reduce.reduce_planes(planes(), window_s=1e-3, n_devices=2)
    assert out["devices_seen"] == 2
    assert abs(out["busy_s"] - (300e-6 + 500e-6) / 2) < 1e-12


def test_inventory_names_planes_and_lines():
    inv = trace_reduce.inventory(planes())
    assert inv[0][0] == "/device:TPU:0"
    assert inv[0][1][1][:2] == ["XLA Ops", 4]


def test_recorded_v5e_trace():
    """A trace recorded on one v5e by ``record_trace.py``: three calls
    of the fit's core-count kernel, host sleeps between them."""
    import json
    from pathlib import Path
    data = Path(__file__).resolve().parent / "data"
    meta = json.loads((data / "v5e_small.json").read_text())
    out = trace_reduce.reduce_dir(data, meta["window_s"], 1)
    assert meta["device_kind"] == "TPU v5 lite"
    assert out["devices_seen"] == 1
    assert 0 < out["busy_s"] < meta["window_s"]
    # the kernel is found by its name in the custom call's text
    assert trace_reduce.op_seconds(out, "eps_count_batch") > 0
    assert out["device_ops"] and len(out["device_ops"]) <= 10
    # the sleeps are the longest idle gaps, named by the host call
    assert out["idle_gaps"][0][1] >= 0.1
    assert sum(v for _, v in out["idle_gaps"]) <= meta["window_s"]
