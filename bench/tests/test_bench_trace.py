"""The reduction from trace events to device metrics, on a made-up trace."""
import pytest

from bench import trace_reduce as tr

E = tr.Event


def _trace():
    dev0 = [E("custom-call.1", 0.0, 1.0, "batch_cluster kernel"),
            E("fusion.2", 0.5, 2.0),
            E("all-reduce.3", 3.0, 4.0),
            E("fusion.2", 9.0, 11.0)]          # mostly after the window
    dev1 = [E("custom-call.7", 1.0, 1.5, "modified_charges"),
            E("collective-permute-done.1", 2.0, 2.5)]
    host = [E("bench.window", 0.0, 5.0), E("bench.execute", 2.2, 2.8),
            E("bench.replan", 4.1, 4.9)]
    return {0: dev0, 1: dev1}, host


def test_busy_union_and_idle_share():
    devs, host = _trace()
    s = tr.reduce_trace(devs, host, tr.window_of(host))
    # device 0 busy [0, 2] + [3, 4] = 3 s; device 1 busy 1 s; mean 2 s
    assert s.window_s == 5.0 and s.devices == 2
    assert s.busy_s == pytest.approx(2.0)
    assert s.idle_share == pytest.approx(0.6)


def test_kernel_sums():
    devs, host = _trace()
    s = tr.reduce_trace(devs, host, (0.0, 5.0))
    assert s.kernel_s == pytest.approx((1.0 + 0.5) / 2)
    ops = dict(s.device_ops)
    assert ops["fusion.2"] == pytest.approx(1.5 / 2)


def test_idle_gaps_are_named_by_the_host_span():
    devs, host = _trace()
    s = tr.reduce_trace(devs, host, (0.0, 5.0))
    gaps = s.idle_gaps
    assert gaps[0] == ("bench.window", pytest.approx(2.5))    # dev1 2.5-5
    assert ("bench.execute", pytest.approx(1.0)) in gaps       # dev0 2-3
    assert ("bench.replan", pytest.approx(1.0)) in gaps       # dev0 4-5


def test_no_device_ops_reads_nothing():
    assert tr.reduce_trace({0: []}, [], (0.0, 1.0)) is None


def test_merging_nested_and_touching_intervals():
    evs = [E("a", 0, 3), E("b", 1, 2), E("c", 3, 4), E("d", 6, 7)]
    assert tr.busy_intervals(evs, 0, 10) == [(0, 4), (6, 7)]
    assert tr.idle_gaps([(0, 4), (6, 7)], 0, 10) == [(4, 6), (7, 10)]
