"""The trace reduction: busy time is the union of device op intervals
inside the harness's window, idle gaps are named by what covers most of
them on the host; checked on synthetic planes and on a trace recorded on
the chip."""
import os

import pytest

import xplane

MS = 1e6  # ns


def _planes():
    ops = [("sort.1", 10 * MS, 20 * MS), ("fusion.2", 25 * MS, 10 * MS),
           ("sort.1", 60 * MS, 10 * MS), ("late", 95 * MS, 20 * MS)]
    host = [("window", 0.0, 100 * MS), ("query", 3 * MS, 42 * MS),
            ("update", 40 * MS, 15 * MS), ("compact", 75 * MS, 10 * MS)]
    return [("/host:CPU", [("python", host)]),
            ("/device:TPU:0", [("XLA Ops", ops), ("Steps", [("x", 0, 1e9)])])]


def test_synthetic_busy_ops_and_gaps():
    r = xplane.reduce_planes(_planes())
    # busy: [10,35] + [60,70] + [95,100] inside the 100 ms window
    assert r.busy_s == pytest.approx(0.040)
    assert r.window_s == pytest.approx(0.100)
    assert r.idle_share == pytest.approx(0.60)
    assert r.device_ops[0] == ["sort.1", pytest.approx(0.030)]
    # [35,60]: update covers 15 ms of it, query 10, nothing 5; [70,95]:
    # compact 10 ms, nothing 15; [0,10]: query 7 ms, nothing 3
    gaps = sorted((name, round(s, 6)) for name, s in r.idle_gaps)
    assert gaps == [(xplane.NO_REQUEST, 0.025), ("query", 0.01),
                    ("update", 0.025)]
    assert r.idle_gaps[0][1] >= r.idle_gaps[-1][1]


def test_no_device_op_in_window_gives_nothing():
    planes = [("/host:CPU", [("python", [("window", 0.0, 1 * MS)])]),
              ("/device:TPU:0", [("XLA Ops", [("op", 5 * MS, MS)])])]
    assert xplane.reduce_planes(planes) is None


def test_recorded_tpu_trace():
    """A trace recorded on one TPU v5e chip by testdata/make_trace.py:
    three sorts, each in a `query` annotation, 20 ms of sleep after each,
    all inside `window`. Host annotations and device ops share one clock,
    so the sorts land inside the window and the sleeps are idle gaps no
    request covers. The trace also holds a device plane with no ops
    (`/device:CUSTOM:Megascale Trace`), which is not a chip."""
    path = os.path.join(os.path.dirname(__file__), "testdata",
                        "small.xplane.pb")
    r = xplane.reduce_file(path)
    assert r is not None and r.n_devices == 1
    assert 0 < r.busy_s < r.window_s
    assert r.window_s >= 0.06  # three sleeps of 20 ms
    assert "sort" in r.device_ops[0][0]
    long_gaps = [name for name, s in r.idle_gaps if s >= 0.015]
    assert long_gaps == [xplane.NO_REQUEST] * 3
