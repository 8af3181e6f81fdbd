"""The trace reduction: busy time is the union of device op intervals
inside the harness's window, idle gaps are named by what covers most of
them on the host; checked on synthetic planes and on a trace recorded on
the chip."""
import os
import types

import pytest

import harness
import phases
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


def _roofline_ctx(device, moved):
    read = types.SimpleNamespace(kind="read", ok=True, t_done=1.0, text="q")
    return types.SimpleNamespace(
        device=device, device_kind="TPU v5 lite", log=[read], t_close=2.0,
        least_bytes={"q": moved},
        peaks={"TPU v5 lite": {"hbm_bytes_per_s": 819e9}})


def test_one_chip_readings_of_the_recorded_trace_are_as_before():
    """The harness now loads the planes once for both reductions; on the
    chip's small trace program_roofline and device_idle_share.complex
    read what the one-chip formulas read before."""
    path = os.path.join(os.path.dirname(__file__), "testdata",
                        "small.xplane.pb")
    r = xplane.reduce_planes(phases.load(path))
    assert r == xplane.reduce_file(path)
    ctx = _roofline_ctx(r, 10**6)
    assert harness.read_metric("program_roofline", ctx) == pytest.approx(
        100.0 * (10**6 / 819e9) / 0.002775731, rel=1e-12)
    assert harness.read_metric("device_idle_share.complex", ctx) == (
        pytest.approx(100.0 * (1 - 0.002775731 / 0.068427206), rel=1e-12))


def test_roofline_over_four_chips_counts_every_chips_peak():
    """Busy time is a mean over the chips, so the bytes the reads need are
    held against four chips' peak: 90% of it reads 90%, not 360%."""
    planes = [("/host:CPU", [("python", [("window", 0.0, 100 * MS)])])]
    for k in range(4):
        planes.append((f"/device:TPU:{k}",
                       [("XLA Ops", [("%sort.1 = s", (10 + k) * MS, 10 * MS)])]))
    r = xplane.reduce_planes(planes)
    assert (r.n_devices, r.busy_s) == (4, pytest.approx(0.010))
    moved = 0.9 * 4 * 819e9 * 0.010
    got = harness.read_metric("program_roofline", _roofline_ctx(r, moved))
    assert got == pytest.approx(90.0) and got <= 100.0
