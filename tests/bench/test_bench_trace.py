"""The trace reduction: busy and idle time, top operations, and idle
gaps named by the benchmark's host spans."""
import os

import pytest

from bench import harness, tracing

SMALL = os.path.join(harness.BENCH, "testdata", "small.xplane.pb")


def test_reduce_synthetic():
    trace = {"ops": {0: [(100, 300, "%while = (s32[]) while(...)"),
                         (120, 200, "%fusion.1 = f32[8] fusion(...)"),
                         (200, 260, "fusion.2"), (500, 600, "fusion.1"),
                         (2000, 2100, "late")]},
             "spans": [(50, 1000, "bench.window"), (90, 320, "bench.unit"),
                       (320, 480, "bench.host"), (480, 700, "bench.unit")]}
    r = tracing.reduce(trace, [0])
    assert r["window_s"] == pytest.approx(950e-9)
    assert r["busy_s"] == [pytest.approx(300e-9)]
    ops = dict(r["device_ops"])          # self time: the loop's own
    assert ops == pytest.approx({"while": 60e-9, "fusion.1": 180e-9,
                                 "fusion.2": 60e-9})
    gaps = dict(r["idle_gaps"])
    assert gaps["bench.host"] == pytest.approx(200e-9)
    assert gaps["bench.window"] == pytest.approx(450e-9)
    assert sum(gaps.values()) + r["busy_s"][0] == pytest.approx(950e-9)


def test_device_clock_is_moved_onto_the_hosts():
    assert tracing._skew([1000, 5000], [2500, 6400]) == -1400
    assert tracing._skew([1000], [2500, 6400]) == 0.0


def test_a_lost_module_event_pairs_by_its_operations():
    """Module events fewer than the host's issues: each run of operations
    outside every module event stands for a lost one, from its first
    operation. Where nothing stands for it, the clocks do not pair."""
    mods = [(1000, 1100, "a"), (3000, 3100, "c")]
    ops = [(1010, 1090, "x"), (2050, 2070, "z"), (2020, 2080, "y"),
           (3010, 3050, "w")]
    issued = [500, 1500, 2500]
    assert tracing._align(ops, mods, issued) == (True, 500)
    assert tracing._align(ops[:1] + ops[3:], mods, issued) == (False, 0.0)
    assert tracing._align(ops, sorted(mods + [(2000, 2100, "b")]),
                          issued) == (True, 500)


def test_reduce_averages_devices_and_needs_a_window():
    trace = {"ops": {0: [(0, 50, "a")], 1: [(0, 100, "a")]},
             "spans": [(0, 100, "bench.window")]}
    r = tracing.reduce(trace, [0, 1])
    assert r["busy_s"] == [pytest.approx(50e-9), pytest.approx(100e-9)]
    assert dict(r["device_ops"])["a"] == pytest.approx(75e-9)
    assert tracing.reduce({"ops": trace["ops"], "spans": []}, [0]) is None
    assert tracing.reduce(trace, [2]) is None


def test_merge():
    assert tracing._merge([(5, 7), (0, 2), (1, 3), (6, 9)]) == [[0, 3],
                                                              [5, 9]]


def test_reduce_recorded_chip_trace():
    """A small trace recorded on a TPU v5e by
    ``bench/testdata/record_small_trace.py``: three units of a jitted
    loop with host-only spans between them."""
    r = tracing.reduce(tracing.load(SMALL), [0])
    assert r is not None
    assert 0 < r["busy_s"][0] < r["window_s"]
    assert r["device_ops"] and all(t > 0 for _, t in r["device_ops"])
    gaps = dict(r["idle_gaps"])
    assert gaps.get("bench.host", 0) > 0
    assert r["busy_s"][0] + sum(gaps.values()) == pytest.approx(
        r["window_s"], rel=1e-6)
