"""The four-chip fit cell's readers: ``fit4.host_shard_s`` averages the
window's fits over the distributed fit's stage histograms,
``fit4.collective_s`` sums the collectives' device time over the
devices seen, and ``fit4.halo_rows`` reads the halo counter's growth
over the window; each finds nothing where the program or the trace has
nothing to read."""

from types import SimpleNamespace as NS

import pytest

from bench import main, trace_reduce
from repro import obs
from repro.obs.metrics import MetricsRegistry

STAGES = ("dist.fit.pack_s", "dist.fit.transfer_s", "dist.fit.unpack_s")


@pytest.fixture
def reg(monkeypatch):
    r = MetricsRegistry()
    monkeypatch.setattr(obs, "registry", lambda: r)
    return r


def _read(metric, fits, **record):
    return main.load_module("metrics", metric).read(
        {"fits": [{"s": 1.0, "attempts": 1}] * fits, **record})


def test_host_shard_sums_the_stage_means_of_the_window_fits(reg):
    # set-up's warm fit first, then the window's two
    for p, t, u in ((9.0, 9.0, 9.0), (1.0, 0.25, 0.5), (2.0, 0.75, 1.5)):
        for h, v in zip(STAGES, (p, t, u)):
            reg.histogram(h).observe(v)
    assert _read("fit4.host_shard_s", 2) == pytest.approx(1.5 + 0.5 + 1.0)


@pytest.mark.parametrize("present", [(), STAGES[:2], STAGES[1:]])
def test_host_shard_reads_none_without_every_histogram(reg, present):
    for h in present:
        reg.histogram(h).observe(1.0)
    assert _read("fit4.host_shard_s", 1) is None
    assert reg.names() == sorted(present)     # the reader made none


def test_host_shard_reads_none_with_fewer_observations_than_fits(reg):
    for h in STAGES:
        reg.histogram(h).observe(1.0)
    assert _read("fit4.host_shard_s", 2) is None


def test_halo_rows_reads_the_counter_per_window_fit(reg):
    reg.counter("dist.halo.rows").inc(1000)          # set-up's fit
    start = {"dist.halo.rows": 1000}
    reg.counter("dist.halo.rows").inc(2 * 1000)      # the window's two
    assert _read("fit4.halo_rows", 2, counters_at_start=start) == 1000.0


@pytest.mark.parametrize("counter,start", [
    (False, {}),                         # the program has no counter
    (True, {}),                          # no value kept at the start
])
def test_halo_rows_reads_none_without_the_counter(reg, counter, start):
    if counter:
        reg.counter("dist.halo.rows").inc(5)
    assert _read("fit4.halo_rows", 1, counters_at_start=start) is None


def ev(name, start_us, dur_us):
    return NS(name=name, start_ns=int(start_us * 1000),
              duration_ns=int(dur_us * 1000))


def _device(k, ops):
    return NS(name=f"/device:TPU:{k}",
              lines=[NS(name="XLA Ops", events=ops)])


def _trace(n_devices, ops_of):
    planes = [_device(k, ops_of(k)) for k in range(n_devices)]
    planes.append(NS(name="/host:CPU", lines=[
        NS(name="python", events=[ev("bench.fit", 0, 10_000)])]))
    return trace_reduce.reduce_planes(planes, window_s=0.01,
                                      n_devices=n_devices)


def _spmd_ops(k):
    """One shard's ops, instruction text as the profiler names them:
    asynchronous permutes as start/done pairs, plain all-gather and
    all-reduce, and compute that must not count (a custom call whose
    operands are collectives' results among it)."""
    return [
        ev("%collective-permute-start.1 = (f32[8,3]) "
           "collective-permute-start(%fusion.2)", 0, 10 * (k + 1)),
        ev("%collective-permute-done.1 = f32[8,3] "
           "collective-permute-done(%collective-permute-start.1)", 20, 30),
        ev("%all-gather.10 = s32[4,16,2] all-gather(%fusion.7)", 100, 40),
        ev("%all-reduce.23 = u32[1] all-reduce(%fusion.8)", 200, 20),
        ev("%fusion.2 = f32[8,3] fusion(%param.1)", 300, 1000),
        ev("%custom-call.5 = f32[8] custom-call(%all-gather.10), "
           "custom_call_target=\"tpu_custom_call\", "
           "backend_config={\"custom_call_config\": {\"name\": "
           "\"eps_count_batch\"}}", 2000, 500),
    ]


def test_collectives_divide_by_the_devices_seen():
    tr = _trace(4, _spmd_ops)
    # per device: permute start 10(k+1), done 30, gather 40, reduce 20 us
    per_device = [(10 * (k + 1) + 30 + 40 + 20) * 1e-6 for k in range(4)]
    want = sum(per_device) / 4 / 2
    got = _read("fit4.collective_s", 2, trace=tr)
    assert got == pytest.approx(want, rel=1e-9)


def test_collectives_match_plain_and_paired_names():
    names = {"collective-permute.3": 1.0,
             "%collective-permute-start.4": 2.0,
             "%collective-permute-done.4": 4.0,
             "all-gather-start.1": 8.0, "%all-gather-done.1": 16.0,
             "%all-reduce.2": 32.0, "all-reduce-start.7": 64.0,
             "%fusion.all-gather.1": 128.0, "%gather.3": 256.0,
             "%custom-call.1 all-reduce_kernel": 512.0}
    tr = {"op_s": names, "devices_seen": 1, "custom_calls": {}}
    assert _read("fit4.collective_s", 1, trace=tr) == 127.0


@pytest.mark.parametrize("trace", [
    None,                                               # untraced run
    {"op_s": {"%fusion.1": 1.0}, "devices_seen": 1},    # no collective
    {"op_s": {"%all-gather.1": 1.0}, "devices_seen": 0},  # no device
])
def test_collectives_read_none_with_nothing_to_read(trace):
    assert _read("fit4.collective_s", 1, trace=trace) is None
