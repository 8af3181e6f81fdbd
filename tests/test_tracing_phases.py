"""Serving phases on the profiler's clock and plan operators named on the
device: the per-request spans that close a read's latency budget on every
execution path, the `mapsq.*` annotations each serving thread opens (and
that the untraced path never builds), and the plan-operator scopes a
compiled program carries in its HLO metadata."""
import collections
import threading

import pytest

from repro.core import executor as ex
from repro.obs import Tracer
from repro.obs import trace as obs_trace
from repro.serve.sparql_server import SPARQLServer
from repro.sparql import lubm
from repro.sparql.engine import PendingDecode, QueryEngine
from repro.sparql.store import store_from_string_triples

from tests.test_serving_pipeline import (
    PAD_QUERIES,
    QUERIES,
    padding_store,
    pipeline_store,
)
from tests.test_sharded import run_prog

SERVED = ("queue_wait", "prepare", "batch_wait", "stage", "dispatch",
          "decode_wait", "transfer", "decode")
ENGINE = ("batch_wait", "stage", "dispatch", "decode_wait", "transfer",
          "decode")
TWO_JOINS = lubm.PREFIX + (
    "SELECT ?x ?y ?z WHERE { ?x ub:memberOf ?z . "
    "?z ub:subOrganizationOf ?y . ?x rdf:type ub:GraduateStudent . }")
L2 = lubm.PREFIX + (
    "SELECT ?x ?y WHERE { ?x rdf:type ub:Course . ?x ub:name ?y . }")


def _missing(trace, names) -> list[str]:
    return [n for n in names if not trace.find(n)]


def _pipelined(eng, tracer, prepared):
    """run_batch_pipelined with a trace per handle, every slot resolved
    and every trace finished; returns (outcomes, traces)."""
    traces = [tracer.new_trace("query") for _ in prepared]
    outcomes = eng.run_batch_pipelined(prepared, traces=traces)
    for i, oc in enumerate(outcomes):
        if isinstance(oc, PendingDecode):
            outcomes[i] = oc.resolve()
    for t in traces:
        tracer.finish(t)
    return outcomes, traces


class _Recorder:
    """Stands in for jax.profiler.TraceAnnotation: notes each annotation's
    name and the thread that opened it."""

    opened: list = []

    def __init__(self, name, **stats):
        self.name = name
        _Recorder.opened.append((name, threading.get_ident(), stats))

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return None


@pytest.fixture
def annotations(monkeypatch):
    _Recorder.opened = []
    monkeypatch.setattr(obs_trace, "TraceAnnotation", _Recorder)
    return _Recorder.opened


# ----------------------------------------------------- spans per path


def test_solo_and_cold_reads_through_the_server_carry_every_span():
    """One client: the first read of a shape is cold (calibration and
    compile), the next ones run alone on the warm solo path; each carries
    the whole budget with zero open spans."""
    tracer = Tracer()
    srv = SPARQLServer(QueryEngine(pipeline_store(), tracer=tracer))
    try:
        for _ in range(3):
            srv.query(QUERIES[0])
    finally:
        srv.close()
    traces = srv.recent_traces()
    assert len(traces) == 3
    for t in traces:
        assert _missing(t, SERVED) == [], t.tree_str()
        assert not t.find("dispatch")[0].attrs.get("stacked")
        assert "lock_wait_ms" in t.find("stage")[0].attrs
    assert traces[0].find("compile")
    assert traces[0].find("dispatch")[0].attrs.get("calibration")
    assert not traces[2].find("compile")
    assert tracer.open_span_count() == 0


def test_stacked_lanes_share_the_chunk_stage_and_dispatch():
    tracer = Tracer()
    eng = QueryEngine(pipeline_store(), tracer=tracer)
    eng.prepare(QUERIES[0]).run()  # warm the shape
    _, traces = _pipelined(eng, tracer,
                           [eng.prepare(QUERIES[0]) for _ in range(4)])
    for t in traces:
        assert _missing(t, ENGINE) == [], t.tree_str()
    stages = [t.find("stage")[0] for t in traces]
    dispatches = [t.find("dispatch")[0] for t in traces]
    assert len({s.attrs["dispatch_id"] for s in stages}) == 1
    assert {s.attrs["dispatch_id"] for s in stages} == {
        d.attrs["dispatch_id"] for d in dispatches}
    # one interval on the shared clock (span times are per-trace offsets)
    assert len({(round(t.origin + s.t0, 9), round(t.origin + s.t1, 9))
                for t, s in zip(traces, stages)}) == 1
    assert sorted(s.attrs["lane"] for s in stages) == [0, 1, 2, 3]
    assert all(s.attrs["lock_wait_ms"] >= 0 for s in stages)
    # the lanes' stage ends before their shared launch starts
    assert stages[0].t1 <= dispatches[0].t0
    assert tracer.open_span_count() == 0


def test_padded_group_lanes_carry_every_span():
    tracer = Tracer()
    eng = QueryEngine(padding_store(), tracer=tracer)
    ps = [eng.prepare(t) for t in PAD_QUERIES for _ in range(2)]
    for p in ps:
        p.run()
    _, traces = _pipelined(eng, tracer, ps)
    assert eng.last_batch[0].padded
    for t in traces:
        assert _missing(t, ENGINE) == [], t.tree_str()
        assert t.find("stage")[0].attrs["stacked"]
    assert tracer.open_span_count() == 0


def test_fallback_reads_carry_spans_and_the_culprit_closes_its_trace():
    """A chunk whose regrow passes max_capacity falls back to the
    sequential path: its good reads carry solo spans, the failing one
    records none of the dispatch's and still leaves nothing open."""
    triples = [(f"<s{i}>", "<p1>", "<m1>") for i in range(8)]
    triples.append(("<m1>", "<qq>", "<z0>"))
    triples += [(f"<t{i}>", "<p2>", "<m2>") for i in range(8)]
    triples += [("<m2>", "<qq>", f"<w{j}>") for j in range(7)]
    tracer = Tracer()
    eng = QueryEngine(store_from_string_triples(triples), max_capacity=16,
                      tracer=tracer)
    ok = eng.prepare("SELECT ?x ?z WHERE { ?x <p1> ?y . ?y <qq> ?z . }")
    boom = eng.prepare("SELECT ?x ?z WHERE { ?x <p2> ?y . ?y <qq> ?z . }")
    ok.run()
    outcomes, traces = _pipelined(eng, tracer, [ok, boom, ok])
    assert eng.last_batch[0].fallback
    assert isinstance(outcomes[1], MemoryError)
    for i in (0, 2):
        assert _missing(traces[i], ENGINE) == [], traces[i].tree_str()
        assert not traces[i].find("dispatch")[-1].attrs.get("stacked")
    assert tracer.open_span_count() == 0


def test_eager_engine_reads_carry_stage_and_dispatch():
    tracer = Tracer()
    eng = QueryEngine(pipeline_store(), compiled=False, tracer=tracer)
    _, traces = _pipelined(eng, tracer,
                           [eng.prepare(q) for q in QUERIES])
    for t in traces:
        assert _missing(t, ENGINE) == [], t.tree_str()
        assert t.find("dispatch")[0].attrs.get("eager")
    assert tracer.open_span_count() == 0


def test_sharded_reads_carry_spans_on_four_devices():
    out = run_prog("tests/distributed/sharded_trace_prog.py", "4",
                   timeout=600)
    assert "SHARDED TRACE SPANS OK n_dev=4" in out


def test_every_read_through_the_server_carries_dispatch_and_decode():
    """Eight clients over four shapes, first reads cold: alone, stacked or
    cold, every answered read has dispatch, transfer and decode spans (a
    one-read chunk used to run untraced), and queue_wait measures the wait
    from submit to its batch's start."""
    tracer = Tracer(ring_size=256)
    srv = SPARQLServer(QueryEngine(pipeline_store(), tracer=tracer),
                       max_wait_s=0.01)
    try:
        def client(k):
            for j in range(4):
                srv.query(QUERIES[(k + j) % len(QUERIES)])

        ts = [threading.Thread(target=client, args=(k,)) for k in range(8)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
    finally:
        srv.close()
    traces = srv.recent_traces()
    assert len(traces) == 32
    for t in traces:
        assert t.root.attrs["outcome"] == "ok"
        assert _missing(t, SERVED) == [], t.tree_str()
        q = t.find("queue_wait")[0]
        assert q.t0 >= 0 and q.t1 >= q.t0
    kinds = collections.Counter(
        "stacked" if t.find("dispatch")[0].attrs.get("stacked")
        else "cold" if t.find("compile") else "solo" for t in traces)
    assert kinds["cold"] >= 1 and kinds["solo"] + kinds["stacked"] >= 1
    assert tracer.open_span_count() == 0


def test_read_spans_tile_its_latency():
    """Warm two-join reads one at a time: the budget's spans follow one
    another, without overlap, inside the request, and on average cover at
    least 90% of the server's part of it, from submit to the end of
    decode. (How much of the client's whole request they cover is a chip
    measurement: a loaded CPU delays the client's wake-up after the decode
    worker resolves. The reads are sized so that the few tens of
    microseconds of host work between two spans stay a small share.)"""
    tracer = Tracer()
    srv = SPARQLServer(QueryEngine(lubm.generate(scale=4), tracer=tracer))
    try:
        for _ in range(6):
            srv.query(TWO_JOINS)
    finally:
        srv.close()
    served = covered = 0.0
    for t in srv.recent_traces()[2:]:
        spans = sorted((s for n in SERVED for s in t.find(n)),
                       key=lambda s: s.t0)
        assert [s.name for s in spans] == list(SERVED)
        for a, b in zip(spans, spans[1:]):
            assert a.t1 <= b.t0 + 1e-9, (a, b)
        assert spans[0].t0 >= 0 and spans[-1].t1 <= t.root.t1
        served += spans[-1].t1 - spans[0].t0
        covered += sum(s.duration_s for s in spans)
    assert covered >= 0.9 * served, (covered, served)


# ------------------------------------------------ profiler annotations


def test_untraced_path_builds_no_annotation(annotations):
    srv = SPARQLServer(QueryEngine(pipeline_store()))
    try:
        for q in QUERIES:
            srv.query(q)
        srv.engine.run_batch([srv.engine.prepare(QUERIES[0])] * 3)
    finally:
        srv.close()
    assert annotations == []


def test_each_serving_thread_opens_its_phases(annotations):
    tracer = Tracer()
    srv = SPARQLServer(QueryEngine(pipeline_store(), tracer=tracer))
    try:
        srv.query(QUERIES[0])
        srv.query(QUERIES[0])
    finally:
        srv.close()
    by_thread = collections.defaultdict(set)
    for name, tid, _ in annotations:
        by_thread[tid].add(name)
    batcher = [n for n in by_thread.values() if "mapsq.wait" in n]
    assert len(batcher) == 1
    assert {"mapsq.wait", "mapsq.collect", "mapsq.batch", "mapsq.prepare",
            "mapsq.stage", "mapsq.launch", "mapsq.sync"} <= batcher[0]
    decode = [n for n in by_thread.values() if "mapsq.decode" in n]
    assert decode and all("mapsq.transfer" in n for n in decode)
    assert all("mapsq.wait" not in n for n in decode)
    # each launch names the executable it runs, as op_scopes() keys it
    launched = {st["module"] for name, _, st in annotations
                if name == "mapsq.launch"}
    assert launched and launched <= set(srv.engine.op_scopes())


# ------------------------------------------- plan operators on device


def test_scope_of_reads_plan_operators_from_op_names():
    assert ex.scope_of(
        "jit(run)/join2/count/jit(searchsorted)/vmap()/while") == (
        "join2/count")
    assert ex.scope_of("jit(run_lane)/vmap(join0)/sort/jit(sort)/sort") == (
        "join0/sort")
    assert ex.scope_of("jit(local_run)/shard_map/join1/shuffle/all_to_all"
                       ) == "join1/shuffle"
    assert ex.scope_of("jit(run)/filter/and") == "filter"
    # a primitive's own name is never a scope, and phases live in joins
    assert ex.scope_of("jit(run)/slice") == ""
    assert ex.scope_of("jit(run)/join0/map/slice") == "join0/map"
    assert ex.scope_of("jit(run)/distinct/sort/sort") == "distinct"
    assert ex.scope_of("sort") == ""


def test_hlo_op_scopes_give_fusions_their_callees_scope():
    text = "\n".join([
        "HloModule jit_run, is_scheduled=true",
        "",
        "%fused_computation.3 (param_0: s32[8]) -> s32[8] {",
        '  %param_0 = s32[8]{0} parameter(0)',
        '  ROOT %add.1 = s32[8]{0} add(%param_0, %param_0), '
        'metadata={op_name="jit(run)/join1/expand/add"}',
        "}",
        "",
        "ENTRY %main.9 (x.1: s32[8]) -> s32[8] {",
        '  %x.1 = s32[8]{0} parameter(0), metadata={op_name="x"}',
        '  %sort.2 = s32[8]{0} sort(%x.1), dimensions={0}, '
        'metadata={op_name="jit(run)/join0/sort/jit(sort)/sort" '
        'stack_frame_id=3}',
        "  ROOT %fusion.3 = s32[8]{0} fusion(%sort.2), kind=kLoop, "
        "calls=%fused_computation.3",
        "}",
    ])
    scopes = ex.hlo_op_scopes(text)
    assert scopes["sort.2"] == "join0/sort"
    assert scopes["fusion.3"] == "join1/expand"
    assert scopes["x.1"] == ""


def test_op_scopes_name_join_phases_of_a_compiled_l2_plan():
    eng = QueryEngine(lubm.generate(scale=1))
    pq = eng.prepare(L2)
    pq.run()  # cold: calibrates and compiles the solo program
    eng.run_batch([pq, pq])  # the stacked width-2 program
    scopes = eng.op_scopes()
    assert len(scopes) == 2
    assert {k.split("(")[0] for k in scopes} == {"jit_run", "jit_run_lane"}
    for key, m in scopes.items():
        named = set(m.values())
        assert {"join0/map", "join0/sort", "join0/count",
                "join0/expand"} <= named, (key, sorted(named))
        assert not any(s.startswith("join1") for s in named)
    lane = next(m for k, m in scopes.items() if k.startswith("jit_run_lane"))
    assert {"scan0", "scan1"} <= set(lane.values())


def test_op_scopes_name_the_matrix_join_layout():
    eng = QueryEngine(pipeline_store(), join_backend="matrix")
    eng.prepare(QUERIES[0]).run()
    named = {s for m in eng.op_scopes().values() for s in m.values()}
    assert {"join0/layout", "join0/expand"} <= named, sorted(named)


def test_scopes_do_not_change_a_plans_answers():
    """Named scopes are metadata only: the compiled program still agrees
    with the eager operator loop."""
    store = pipeline_store()
    compiled = QueryEngine(store)
    eager = QueryEngine(store, compiled=False)
    for q in QUERIES:
        a = sorted(map(sorted, (r.items() for r in compiled.query(q))))
        b = sorted(map(sorted, (r.items() for r in eager.query(q))))
        assert a == b, q
