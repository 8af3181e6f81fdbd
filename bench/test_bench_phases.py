"""The phase reduction: the device's idle time split by what the batcher
thread was doing, device time by plan operator, the new per-read metric
readers and the latency budget; checked on synthetic planes and on a
trace recorded on the chip. The earlier reduction reads the chip's small
trace as it always has."""
import dataclasses
import json
import os
import types

import pytest

import phase_report
import phases
import xplane

MS = 1e6  # ns
HERE = os.path.dirname(os.path.abspath(__file__))


def _host(name, spans):
    return (name, [("mapsq." + p, a * MS, (b - a) * MS) for p, a, b in spans])


def _planes():
    batcher = _host("batcher", [
        ("wait", 0, 10), ("collect", 10, 12), ("prepare", 12, 15),
        ("stage", 15, 20), ("launch", 20, 22), ("sync", 22, 40),
        ("wait", 40, 70), ("collect", 70, 71), ("prepare", 71, 75),
        ("stage", 75, 80), ("launch", 80, 81), ("sync", 81, 96),
        ("batch", 12, 40), ("batch", 71, 98)])
    decoder = _host("decoder", [("transfer", 40, 45), ("decode", 45, 60)])
    window = ("main", [("window", 0.0, 100 * MS)])
    ops = [("%fusion.1 = s32[8] fusion(%p)", 22 * MS, 8 * MS),
           ("%while.2 = (s32[]) while(%t)", 30 * MS, 10 * MS),
           ("%fusion.9 = s32[8] fusion(%q)", 31 * MS, 2 * MS),
           ("%sort.3 = s32[8] sort(%x)", 81 * MS, 9 * MS)]
    # the trace numbers a module its own way; the launch annotation names
    # the executable as op_scopes() keys it
    modules = [("jit_run(1)", 20.5 * MS, 20.5 * MS),
               ("jit_run_lane(2)", 80.5 * MS, 10.5 * MS)]
    launches = (phases.LAUNCHES, [("jit_run(a1)", 20 * MS, 1 * MS),
                                  ("jit_run_lane(b2)", 80 * MS, 0.5 * MS)])
    return [("/host:CPU", [window, batcher, decoder, launches]),
            ("/device:TPU:0", [("XLA Modules", modules), ("XLA Ops", ops)])]


SCOPES = {"jit_run(a1)": {"fusion.1": "join0/sort", "while.2": "join1/count",
                          "fusion.9": "join1/count", "p": ""},
          "jit_run_lane(b2)": {"sort.3": "distinct"}}


def test_idle_split_by_batcher_phase_sums_to_the_idle_share():
    r = phases.reduce_planes(_planes(), SCOPES)
    assert r.window_s == pytest.approx(0.100)
    assert r.busy_s == pytest.approx(0.027)  # [22,40] + [81,90]
    got = {k: round(v * 1e3, 6) for k, v in r.idle_s.items()}
    assert got == {"dispatch": 20.0, "sync": 6.0, "batch": 2.0,
                   "decode": 20.0, "wait": 23.0, "other": 0.0, "none": 2.0}
    assert sum(r.idle_s.values()) == pytest.approx(r.window_s - r.busy_s)
    # the same idle share the earlier reduction reads
    idle = xplane.reduce_planes(_planes()).idle_share
    assert sum(r.idle_share(p) for p in phases.PARTS) == pytest.approx(idle)
    assert r.batcher_s["sync"] == pytest.approx(0.033)
    assert r.decode_s == {"transfer": pytest.approx(0.005),
                          "decode": pytest.approx(0.015)}
    # longest gap [40,81]: the batcher mostly waited, a decode ran 20 of 41
    sec, phase, dec = r.gaps[0]
    assert (round(sec * 1e3, 6), phase) == (41.0, "wait")
    assert dec == pytest.approx(20 / 41)
    assert [g[1] for g in r.gaps] == ["wait", "wait", "sync"]


def test_device_time_by_plan_operator():
    r = phases.reduce_planes(_planes(), SCOPES)
    assert (r.modules, r.modules_known, r.modules_unscoped) == (2, 2, 0)
    assert r.module_keys == [("jit_run(1)", "jit_run(a1)"),
                             ("jit_run_lane(2)", "jit_run_lane(b2)")]
    # the fusion inside the while's event is the loop's body: its time is
    # the while's, counted once
    assert r.scope_s == {"distinct": pytest.approx(0.009),
                         "join0/sort": pytest.approx(0.008),
                         "join1/count": pytest.approx(0.010)}
    assert r.scoped_s == pytest.approx(r.busy_s)
    assert r.join_s == pytest.approx(0.018)
    assert r.top_ops[0][1:] == [pytest.approx(0.010), "join1/count"]
    assert [op[0].split()[0] for op in r.top_ops] == [
        "%while.2", "%sort.3", "%fusion.1"]


def test_no_or_unscoped_executables_give_no_operator_numbers():
    r = phases.reduce_planes(_planes(), None)
    assert r.join_s is None and r.scope_s is None and r.top_ops[0][2] is None
    # a module compiled without scopes (a build before them, from the
    # persistent compile cache) must not read as zero join time
    blank = {k: {i: "" for i in m} for k, m in SCOPES.items()}
    r = phases.reduce_planes(_planes(), blank)
    assert r.modules_unscoped == 2 and r.join_s is None
    # a module op_scopes() does not know is left out, not guessed
    r = phases.reduce_planes(_planes(),
                             {"jit_run(a1)": SCOPES["jit_run(a1)"]})
    assert r.modules_known == 1 and r.join_s == pytest.approx(0.018)
    assert r.scoped_s == pytest.approx(0.018)
    # a module whose name is not the last launch's is tied to nothing
    planes = _planes()
    dev = dict(planes[1][1])
    dev["XLA Modules"] = [("jit_other(7)",) + dev["XLA Modules"][1][1:],
                          dev["XLA Modules"][0]]
    r = phases.reduce_planes(
        [planes[0], ("/device:TPU:0", list(dev.items()))], SCOPES)
    assert r.modules_known == 1 and r.scope_s.get("distinct") is None


def test_module_starting_just_before_its_launch_is_still_its_own():
    """Host and device clocks agree only to about 0.1 ms: a module may
    appear to start before its launch's annotation."""
    planes = _planes()
    host = dict(planes[0][1])
    host[phases.LAUNCHES] = [("jit_run(a1)", 20 * MS, 1 * MS),
                             ("jit_run_lane(b2)", 80.6 * MS, 0.5 * MS)]
    r = phases.reduce_planes([("/host:CPU", list(host.items())), planes[1]],
                             SCOPES)
    assert r.module_keys == [("jit_run(1)", "jit_run(a1)"),
                             ("jit_run_lane(2)", "jit_run_lane(b2)")]


def test_trace_without_batcher_phases_gives_nothing():
    planes = _planes()
    host = [ln for ln in planes[0][1] if ln[0] != "batcher"]
    assert phases.reduce_planes([("/host:CPU", host), planes[1]]) is None


def test_small_recorded_trace_reads_as_before():
    """The chip's small trace through the earlier reduction: the numbers
    it gave when it was recorded."""
    r = xplane.reduce_file(os.path.join(HERE, "testdata", "small.xplane.pb"))
    assert r.busy_s == pytest.approx(0.002775731, abs=1e-12)
    assert r.window_s == pytest.approx(0.068427206, abs=1e-12)
    assert (r.n_devices, r.n_ops) == (1, 7)
    assert [round(s, 12) for _, s in r.device_ops] == [
        0.002761625, 1.2734e-05, 1.372e-06]
    assert r.device_ops[0][0].startswith("%sort.6 = ")
    assert [(n, round(s, 12)) for n, s in r.idle_gaps] == [
        (xplane.NO_REQUEST, 0.022695743), (xplane.NO_REQUEST, 0.021541123),
        (xplane.NO_REQUEST, 0.021414606), (xplane.NO_REQUEST, 2e-09),
        (xplane.NO_REQUEST, 1e-09)]


def test_recorded_tpu_phase_trace():
    """A trace recorded on one TPU v5e chip by testdata/make_phase_trace.py:
    LUBM(1) reads through SPARQLServer with a tracer, two clients sending a
    two-join and then a one-join read, stacked two wide. In the chip's own
    format the batcher's and the decode workers' annotations split the
    device's idle time, each module run is tied through its launch to an
    executable the scopes name, and plan operators name the busy time."""
    data = os.path.join(HERE, "testdata")
    planes = phases.load(os.path.join(data, "phases.xplane.pb"))
    with open(os.path.join(data, "phases.scopes.json")) as f:
        scopes = json.load(f)
    # recorded on the chip, not re-recorded on a host without one: the
    # device plane the reductions read is a TPU's
    busy = [p for p, lines in planes if p.startswith("/device:") and any(
        lname in xplane.OPS_LINES and evs for lname, evs in lines)]
    assert busy and all(p.startswith("/device:TPU:") for p in busy)
    r = phases.reduce_planes(planes, scopes)
    assert r is not None
    idle = xplane.reduce_planes(planes).idle_share
    assert sum(r.idle_s.values()) / r.window_s == pytest.approx(idle)
    assert {"wait", "collect", "batch", "prepare", "stage", "launch",
            "sync"} <= set(r.batcher_s)
    assert set(r.decode_s) == {"transfer", "decode"}
    # the batcher's annotations cover its thread: little idle time is
    # left with no phase to name it
    assert r.idle_s["none"] < 0.05 * r.window_s
    assert r.modules > 0 and r.modules_known == r.modules
    assert r.modules_unscoped == 0
    # one executable per module the trace names, and the other way round
    assert len({n for n, _ in r.module_keys}) == len(r.module_keys)
    assert len({k for _, k in r.module_keys}) == len(r.module_keys)
    assert r.scoped_s >= 0.9 * r.busy_s
    assert r.join_s == pytest.approx(r.scoped_s, rel=0.01)
    assert {s.split("/")[0] for s in r.scope_s} >= {"join0", "join1"}
    # the heaviest ops are the searchsorted loops of a join's count or
    # expand phase, not its sort
    name, _, scope = r.top_ops[0]
    assert name.startswith("%while.") and scope.split("/")[1] in (
        "count", "expand")


# -------------------------------------------- readers and the budget


def _span(name, t0, t1):
    return types.SimpleNamespace(name=name, t0=t0, t1=t1,
                                 duration_s=t1 - t0, open=False)


class _Trace:
    def __init__(self, outcome, spans, total):
        self.root = types.SimpleNamespace(name="query",
                                          attrs={"outcome": outcome})
        self.spans = [_span(*s) for s in spans]
        self.duration_s = total

    def find(self, name):
        return [s for s in self.spans if s.name == name]

    def open_spans(self):
        return []


def _reader(name):
    import harness

    return lambda ctx: harness.read_metric(name, ctx)


def test_span_readers_average_answered_reads():
    traces = [_Trace("ok", [("queue_wait", 0, 0.004), ("stage", 0.01, 0.012)],
                     0.05),
              _Trace("ok", [("queue_wait", 0, 0.002), ("stage", 0.01, 0.011)],
                     0.05),
              _Trace("timeout", [("queue_wait", 0, 1.0)], 1.0)]
    ctx = types.SimpleNamespace(traces=traces)
    assert _reader("queue_wait_ms_per_query.complex")(ctx) == (
        pytest.approx(3.0))
    assert _reader("stage_ms_per_query.complex")(ctx) == pytest.approx(1.5)
    # a program that records no such span (the parent's) reads nothing
    bare = types.SimpleNamespace(traces=[_Trace("ok", [], 0.05)])
    assert _reader("queue_wait_ms_per_query.complex")(bare) is None
    assert _reader("stage_ms_per_query.complex")(bare) is None


def test_budget_sums_the_read_spans():
    t = _Trace("ok", [("queue_wait", 0, 0.01), ("prepare", 0.01, 0.011),
                      ("parse", 0.0101, 0.0105), ("batch_wait", 0.011, 0.02),
                      ("stage", 0.02, 0.021), ("dispatch", 0.021, 0.05),
                      ("decode_wait", 0.05, 0.06), ("transfer", 0.06, 0.07),
                      ("decode", 0.07, 0.09)], 0.095)
    b = phase_report.budget([t, _Trace("error", [], 0.2)])
    assert b["reads"] == 1
    assert b["read_ms"] == pytest.approx(95.0)
    assert b["sum_ms"] == pytest.approx(90.0)  # parse sits inside prepare
    assert b["spans_ms"]["parse"] == pytest.approx(0.4)
    assert b["reads_missing"]["compile"] == 1
    assert b["reads_missing"]["dispatch"] == 0
    assert b["covers_read"]  # 90 of 95 ms


def test_budget_flags_spans_that_leave_the_read_uncovered():
    """A dropped span shows: the rest add up to under 90% of the read."""
    t = _Trace("ok", [("queue_wait", 0, 0.01), ("dispatch", 0.021, 0.05),
                      ("decode", 0.07, 0.09)], 0.095)
    b = phase_report.budget([t])
    assert b["sum_ms"] == pytest.approx(59.0)
    assert not b["covers_read"]
    assert b["reads_missing"]["stage"] == 1
    assert not phase_report.budget([])["covers_read"]


def test_report_fails_when_the_trace_was_never_reduced(monkeypatch, capsys):
    """A harness run that hands back no phase reduction leaves the phases
    unread: the report says so and exits 1 instead of printing null
    phases."""
    import harness

    def run(*args, **kw):
        return {"correct": True, "failed": 0, "metrics": {},
                "device": None}

    monkeypatch.setattr(harness, "run", run)
    monkeypatch.setenv("TPU_LOG_DIR", "unused")
    assert phase_report.main(["--workload", "lubm20.complex", "--seed", "1",
                              "--seconds", "1"]) == 1
    assert "never read" in capsys.readouterr().err


def test_new_metrics_are_declared_for_the_complex_cell():
    with open(os.path.join(HERE, "..", "BENCHMARK.json")) as f:
        bench = json.load(f)
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    for name in ("queue_wait_ms_per_query.complex",
                 "stage_ms_per_query.complex"):
        m = per_layer[name]
        assert (m["source"], m["moves"], m["workloads"]) == (
            "program_span", "read_p95_ms", ["lubm20.complex"])
        assert os.path.exists(os.path.join(HERE, "metrics", name + ".py"))


# -------------------------------------------- several chips


def _ops(*evs):
    return [(name, a * MS, (b - a) * MS) for name, a, b in evs]


def test_exposed_collectives_mean_over_chips():
    chip0 = _ops(("%all-to-all.3 = s32[8] all-to-all(%x)", 10, 30),
                 ("%fusion.1 = s32[8] fusion(%y)", 20, 40),
                 # a loop around a collective is its caller, not work
                 # beside it; the loop body's fusion is
                 ("%while.2 = (s32[]) while(%t)", 50, 80),
                 ("%all-gather-done.4 = s32[8] all-gather-done(%s)", 55, 65),
                 ("%fusion.5 = s32[8] fusion(%z)", 60, 70))
    chip1 = _ops(("%fusion.7 = s32[8] fusion(%p)", 0, 90),
                 # runs past the window's close at 100 ms
                 ("%collective-permute-start.1 = s32[8] "
                  "collective-permute-start(%q)", 95, 110),
                 ("%fusion.8 = s32[8] fusion(%r)", 96, 97))
    # chip 0: [10,20] and [55,60]; chip 1: [95,96] and [97,100]
    got = phases.exposed_collective_s([chip0, chip1], 0.0, 100 * MS)
    assert got == pytest.approx((0.015 + 0.004) / 2)
    # compute alone exposes nothing, and nor does a collective that
    # compute beside it covers throughout
    assert phases.exposed_collective_s(
        [_ops(("%fusion.1 = f", 0, 50))], 0.0, 100 * MS) is None
    assert phases.exposed_collective_s(
        [_ops(("%fusion.1 = f", 0, 15), ("%fusion.3 = f", 15, 50),
              ("%all-reduce.2 = a", 10, 20))], 0.0, 100 * MS) == 0.0


def test_exposed_collectives_through_the_phase_reduction():
    planes = _planes()
    assert phases.reduce_planes(planes, SCOPES).collective_exposed_s is None
    chip1 = ("/device:TPU:1", [("XLA Ops", _ops(
        ("%all-to-all.1 = s32[8] all-to-all(%x)", 10, 30)))])
    r = phases.reduce_planes(planes + [chip1], SCOPES)
    assert r.collective_exposed_s == pytest.approx(0.020 / 2)
    # the serving-phase split still reads the first chip
    assert r.busy_s == pytest.approx(0.027)


def test_phase_metrics_read_off_the_recorded_trace():
    """The three per-layer metrics the harness reads from its phase
    reduction, on the chip's recorded trace: idle while the batcher
    dispatches, idle while rows decode, join device time per read."""
    data = os.path.join(HERE, "testdata")
    with open(os.path.join(data, "phases.scopes.json")) as f:
        scopes = json.load(f)
    ph = phases.reduce_planes(phases.load(os.path.join(data,
                                                       "phases.xplane.pb")),
                              scopes)
    read = types.SimpleNamespace(kind="read", ok=True, t_done=1.0)
    late = types.SimpleNamespace(kind="read", ok=True, t_done=3.0)
    ctx = types.SimpleNamespace(phases=ph, log=[read] * 4 + [late],
                                t_close=2.0)
    window = 0.077687676
    assert ph.window_s == pytest.approx(window)
    assert _reader("idle_dispatch_share.complex")(ctx) == pytest.approx(
        100 * 0.006226838 / window)
    assert _reader("idle_decode_share.complex")(ctx) == pytest.approx(
        100 * 0.015548545 / window)
    assert _reader("join_device_ms_per_query.complex")(ctx) == (
        pytest.approx(1e3 * 0.004776413 / 4))
    # one chip, no collective: the sharded reader finds nothing to read
    assert _reader("collective_exposed_ms_per_query.sharded")(ctx) is None
    # an untraced run, or a module compiled without scopes, reads nothing
    assert _reader("idle_dispatch_share.complex")(
        types.SimpleNamespace(phases=None)) is None
    ctx.phases = dataclasses.replace(ph, join_s=None)
    assert _reader("join_device_ms_per_query.complex")(ctx) is None
    with open(os.path.join(HERE, "..", "BENCHMARK.json")) as f:
        per_layer = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name in ("idle_dispatch_share.complex", "idle_decode_share.complex",
                 "join_device_ms_per_query.complex"):
        assert per_layer[name]["workloads"] == ["lubm20.complex"]
        assert per_layer[name]["moves"] == "queries_per_s"
