"""The trace reduction, the peaks table and the per-layer readers, on a
constructed trace (no chip needed)."""
from types import SimpleNamespace

import pytest

from bench import harness, peaks, tracefile, work
from bench.tracefile import DeviceEvent, HostEvent

MS = 1e6  # ns


def _trace():
    host = [HostEvent("main", "bench_window", 0, 100 * MS),
            HostEvent("main", "bench_fit", 0, 80 * MS),
            HostEvent("main", "bench_observe", 80 * MS, 20 * MS),
            HostEvent("worker", "PjitFunction(step)", 70 * MS, 10 * MS)]
    dev = [DeviceEvent(0, "fusion.1", -5 * MS, 10 * MS),     # clipped: 5 ms
           DeviceEvent(0, "_nll_grad_kernel", 10 * MS, 20 * MS),
           DeviceEvent(0, "fusion.2", 20 * MS, 25 * MS),     # overlaps
           DeviceEvent(0, "custom-call.cholupdate", 90 * MS, 5 * MS),
           DeviceEvent(0, "late", 120 * MS, 5 * MS)]         # outside
    return dev, host


def test_busy_is_a_union_and_idle_share_follows():
    r = tracefile.reduce(*_trace())
    # [0,5) + [10,45) + [90,95) = 45 ms of 100
    assert r.window_s == pytest.approx(0.1)
    assert r.busy_s == pytest.approx(0.045)
    assert r.idle_share == pytest.approx(0.55)
    assert tracefile.idle_percent(SimpleNamespace(trace=r)) \
        == pytest.approx(55.0)


def test_kernel_time_by_name_and_breakdown():
    r = tracefile.reduce(*_trace())
    assert r.kernel_seconds("nll_grad") == (pytest.approx(0.020), 1)
    assert r.kernel_seconds("cholupdate") == (pytest.approx(0.005), 1)
    assert r.kernel_seconds("absent") == (0.0, 0)
    assert [op for op, _ in r.device_ops][:2] == ["fusion.2",
                                                  "_nll_grad_kernel"]
    gaps = dict(r.idle_gaps)
    # the 45 ms gap [45, 90) is mostly under bench_fit; [95, 100) under
    # bench_observe
    assert r.idle_gaps[0][1] == pytest.approx(0.045)
    assert r.idle_gaps[0][0] == "bench_fit"
    assert gaps["bench_observe"] == pytest.approx(0.005)


def test_breakdown_nets_out_nested_ops_and_names_ops_by_instruction():
    host = [HostEvent("main", "bench_window", 0, 100 * MS)]
    dev = [DeviceEvent(0, "while.1", 0, 80 * MS),
           DeviceEvent(0, "fusion.3", 10 * MS, 50 * MS),
           DeviceEvent(0, "copy.4", 65 * MS, 10 * MS)]
    r = tracefile.reduce(dev, host)
    assert r.busy_s == pytest.approx(0.080)
    assert dict(r.device_ops) == {"fusion.3": pytest.approx(0.050),
                                  "while.1": pytest.approx(0.020),
                                  "copy.4": pytest.approx(0.010)}
    hlo = ('%copy.7 = f32[8]{0} copy(f32[8]{0} %cholupdate.1), '
           'frontend_attributes={}')
    assert tracefile.op_name(hlo) == "copy.7"
    assert tracefile.op_name('%cholupdate.1 = f32[8]{0} custom-call('
                             'f32[8]{0} %x), custom_call_target='
                             '"tpu_custom_call"') \
        == "cholupdate.1 (tpu_custom_call)"


def test_peaks_unknown_kind_is_an_error():
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")
    share, bound = peaks.roofline_share(0, 819e9, 2.0, "TPU v5 lite")
    assert (share, bound) == (pytest.approx(50.0), "memory")
    assert peaks.roofline_share(1, 1, 0.0, "TPU v5 lite") is None


def _ctx(r, **counts):
    return SimpleNamespace(trace=r, counts=counts, spans=[],
                           device_kind="TPU v5 lite", cfg={}, traffic={})


def test_missing_kernel_gives_no_metric_not_zero():
    dev, host = _trace()
    r = tracefile.reduce([e for e in dev if "nll" not in e.name], host)
    ctx = _ctx(r, iters=10, agents=4, n=128, dim=2, events=3, window=128,
               fits=1)
    assert harness.metric_reader("nll_grad_roofline")(ctx) is None
    assert harness.metric_reader("cholupdate_roofline")(ctx) is not None
    empty = tracefile.reduce([], host)
    assert harness.metric_reader("device_idle.train")(_ctx(empty)) is None
    assert harness.metric_reader("slot_device_ms.serve")(
        _ctx(empty, batches=3)) is None


def test_metric_family_reader_serves_each_kind():
    r = tracefile.reduce(*_trace())
    for kind in ("serve", "train", "stream"):
        assert harness.metric_reader(f"device_idle.{kind}")(_ctx(r)) \
            == pytest.approx(55.0)


def test_step_shares_read_busy_time_against_the_bound_that_applies():
    r = tracefile.reduce(*_trace())            # 45 ms busy
    ctx = _ctx(r, fits=2, iters=200, agents=4, n=128, dim=2)
    flops = 2 * work.fit(4, 128, 2, 100)
    assert harness.metric_reader("mfu.train")(ctx) \
        == pytest.approx(100 * flops / 0.045 / 197e12)
    ctx = _ctx(r, events=3, agents=4, window=128, dim=2)
    flops, nbytes = work.observe_event(4, 128, 2)
    assert flops / 197e12 < nbytes / 819e9          # memory-bound
    assert harness.metric_reader("mfu.stream")(ctx) \
        == pytest.approx(100 * 3 * nbytes / 819e9 / 0.045)
    empty = tracefile.reduce([], _trace()[1])
    assert harness.metric_reader("mfu.train")(
        _ctx(empty, fits=2, iters=200, agents=4, n=128, dim=2)) is None


def test_roofline_reader_counts_needed_work():
    r = tracefile.reduce(*_trace())
    ctx = _ctx(r, iters=10, agents=4, n=128, dim=2)
    flops, nbytes = work.nll_grad(128, 2)
    want = 100 * 40 * nbytes / 819e9 / 0.020
    assert harness.metric_reader("nll_grad_roofline")(ctx) \
        == pytest.approx(want)
