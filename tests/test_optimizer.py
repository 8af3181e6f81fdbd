"""Cost-based optimizer: statistics catalog, statistics-driven join
ordering (beats the greedy order on the J1/J2 shapes), filter pushdown,
UNION through the whole stack, FILTER `&&`/`||`, plan-cache warmup
persistence, and property-based differential tests that every rewritten
plan returns the same rows as the NumPy oracle and the unoptimized plan."""
import json

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    from _hypothesis_compat import given, settings, st  # noqa: F401
    HAVE_HYPOTHESIS = False

from repro.core import plan_ir
from repro.core.planner import TriplePattern, plan_bgp
from repro.sparql import lubm, optimizer
from repro.sparql.baseline import reference_rows
from repro.sparql.engine import QueryEngine
from repro.sparql.parser import ParseError, parse
from repro.sparql.store import StoreStatistics, store_from_string_triples

UB = "http://swat.cse.lehigh.edu/onto/univ-bench.owl#"
RDF_TYPE = "<http://www.w3.org/1999/02/22-rdf-syntax-ns#type>"
PREFIX = f"PREFIX ub: <{UB}>\n"


def rows_as_sets(rows):
    return sorted(tuple(sorted(r.items())) for r in rows)


def student_store(n_students=15, n_with_advisor=12):
    triples = []
    for i in range(n_students):
        s = f"<s{i}>"
        triples.append((s, RDF_TYPE, f"<{UB}Student>"))
        if i < n_with_advisor:
            triples.append((s, f"<{UB}advisor>", f"<p{i % 4}>"))
        triples.append((s, f"<{UB}age>", str(18 + i)))
        triples.append((s, f"<{UB}name>", f'"student{i}"'))
    return store_from_string_triples(triples)


@pytest.fixture(scope="module")
def j_store():
    return lubm.generate(scale=1, seed=0, join_shapes=True)


# ----------------------------------------------------- statistics catalog


def test_store_statistics_catalog():
    store = store_from_string_triples([
        ("<a>", "<p>", "<x>"),
        ("<a>", "<p>", "<y>"),
        ("<b>", "<p>", "<x>"),
        ("<b>", "<q>", "<x>"),
    ])
    stats = store.statistics
    assert isinstance(stats, StoreStatistics)
    assert stats.n_triples == 4
    assert stats.n_subjects == 2 and stats.n_predicates == 2
    p = store.dictionary.lookup("<p>")
    assert stats.predicates[p].count == 3
    assert stats.predicates[p].n_subjects == 2
    assert stats.predicates[p].n_objects == 2


def test_pattern_cardinality_and_distinct_estimates():
    store = store_from_string_triples(
        [(f"<s{i}>", "<p>", f"<o{i % 3}>") for i in range(12)]
        + [(f"<s{i}>", "<q>", "<z>") for i in range(4)]
    )
    stats = store.statistics
    lk = store.dictionary.lookup
    tp = TriplePattern("?x", "<p>", "?y")
    assert stats.pattern_cardinality(tp, lk) == 12
    assert stats.distinct_values(tp, "?x", lk) == 12
    assert stats.distinct_values(tp, "?y", lk) == 3
    # bound object: count/n_objects under uniformity
    tp2 = TriplePattern("?x", "<p>", "<o0>")
    assert stats.pattern_cardinality(tp2, lk) == pytest.approx(4.0)
    # unknown constants can never match
    assert stats.pattern_cardinality(
        TriplePattern("?x", "<nope>", "?y"), lk
    ) == 0.0


# ------------------------------------- J1/J2: the statistics-order win


@pytest.mark.parametrize("name", ["J1", "J2"])
def test_stats_join_order_beats_greedy_on_j_shapes(j_store, name):
    """Acceptance: on the bad-join-order shapes the statistics-driven
    order produces strictly smaller maximum intermediate join buckets than
    the greedy order, with identical results, and the warm query stays at
    one dispatch with zero compiles."""
    text = lubm.J_QUERIES[name]
    greedy = QueryEngine(j_store, optimize=False)
    stats = QueryEngine(j_store)
    rg = greedy.prepare(text).run()
    ps = stats.prepare(text)
    rs = ps.run()
    assert rows_as_sets(rg.rows) == rows_as_sets(rs.rows)
    assert rs.stats.peak_join_bucket < rg.stats.peak_join_bucket, (
        rs.stats.peak_join_bucket,
        rg.stats.peak_join_bucket,
    )
    # the win is structural, not marginal: an order of magnitude
    assert rs.stats.peak_join_bucket * 8 <= rg.stats.peak_join_bucket
    warm = ps.run()
    assert warm.stats.n_dispatches == 1 and warm.stats.n_compiles == 0
    # explain carries the calibrated buckets and the pass trace
    report = ps.explain()
    assert "join_order[required]" in report
    assert "cache: compiled, join buckets=" in report


def test_exhaustive_start_orders_from_selective_tail(j_store):
    """order_patterns starts J1 from the 12-row tail, not the 10-row type
    scan the greedy heuristic picks (whose only join explodes)."""
    q = parse(lubm.J_QUERIES["J1"])
    order, flags, ests, _backends, _, _moved = optimizer.order_patterns(
        q.patterns,
        j_store.estimate_cardinality,
        j_store.statistics,
        j_store.dictionary.lookup,
    )
    assert not any(flags)  # fully connected: no cross joins
    assert max(ests) <= 16  # every estimated intermediate stays tiny
    # greedy starts at the type scan (index 0, cardinality 10) instead
    steps = plan_bgp(q.patterns, j_store.estimate_cardinality)
    assert steps[0].pattern_index == 0
    assert order[0] != 0


# --------------------------------------------------------- filter pushdown


def test_filter_pushdown_shrinks_join_buckets():
    """A filter on a scan's own variables is applied before the join, so
    the calibrated join bucket shrinks vs the unoptimized plan."""
    store = student_store()
    text = (PREFIX + "SELECT ?x ?a ?n WHERE { ?x ub:age ?a . "
            "?x ub:name ?n . FILTER (?a >= 32) }")
    legacy = QueryEngine(store, optimize=False)
    opt = QueryEngine(store)
    rl = legacy.prepare(text).run()
    ro = opt.prepare(text).run()
    assert rows_as_sets(rl.rows) == rows_as_sets(ro.rows)
    assert len(ro.rows) == 1
    assert ro.stats.peak_join_bucket < rl.stats.peak_join_bucket
    report = opt.prepare(text).explain()
    assert "filter_pushdown" in report and "scan[" in report


def test_pushdown_query_warm_single_dispatch():
    store = student_store()
    eng = QueryEngine(store)
    text = (PREFIX + "SELECT ?x ?n WHERE { ?x ub:age ?a . "
            "?x ub:name ?n . FILTER (?a >= 25 || ?a < 20) }")
    pq = eng.prepare(text)
    pq.run()
    warm = pq.run()
    assert warm.stats.n_dispatches == 1
    assert warm.stats.n_compiles == 0
    assert warm.stats.cache_hits == 1


def test_filter_on_optional_var_stays_after_left_join():
    """Conjuncts reading OPTIONAL-bound variables must not sink into the
    optional side (that would turn filtered rows into unmatched-but-kept
    rows); they attach after the left join and still match the oracle."""
    store = student_store(n_students=8, n_with_advisor=5)
    text = PREFIX + """SELECT ?x ?y WHERE {
        ?x a ub:Student . OPTIONAL { ?x ub:advisor ?y }
        FILTER (?y != <p1>) }"""
    for compiled in (True, False):
        eng = QueryEngine(store, compiled=compiled)
        got = eng.query(text)
        want = reference_rows(store, parse(text))
        assert rows_as_sets(got) == rows_as_sets(want)


def test_projection_prune_in_trace():
    store = student_store()
    # ?n is bound by exactly one pattern and never projected or filtered
    text = PREFIX + "SELECT ?x WHERE { ?x a ub:Student . ?x ub:name ?n . }"
    report = QueryEngine(store).prepare(text).explain()
    assert "projection_prune" in report and "?n" in report


# ------------------------------------------------------------------ UNION


UNION_QUERIES = [
    # shared required part, single-pattern branches
    PREFIX + """SELECT ?x ?v WHERE { ?x a ub:Student .
        { ?x ub:advisor ?v } UNION { ?x ub:name ?v } }""",
    # no required part at all
    PREFIX + """SELECT ?x ?v WHERE {
        { ?x ub:advisor ?v } UNION { ?x ub:age ?v } }""",
    # multi-pattern branch + DISTINCT dedup across branches
    PREFIX + """SELECT DISTINCT ?x WHERE {
        { ?x ub:advisor ?p . ?x ub:age ?a } UNION { ?x ub:name ?n } }""",
    # branch-only variables differ per branch (UNBOUND padding)
    PREFIX + """SELECT ?x ?p ?n WHERE { ?x a ub:Student .
        { ?x ub:advisor ?p } UNION { ?x ub:name ?n } }""",
    # three branches
    PREFIX + """SELECT ?x ?v WHERE { { ?x ub:advisor ?v }
        UNION { ?x ub:name ?v } UNION { ?x ub:age ?v } }""",
]


@pytest.mark.parametrize("compiled", [True, False])
@pytest.mark.parametrize("qi", range(len(UNION_QUERIES)))
def test_union_matches_oracle(compiled, qi):
    store = student_store()
    eng = QueryEngine(store, compiled=compiled)
    text = UNION_QUERIES[qi]
    got = eng.query(text)
    want = reference_rows(store, parse(text))
    assert rows_as_sets(got) == rows_as_sets(want), text


def test_union_keeps_duplicates_multiset_semantics():
    triples = [("<a>", "<p>", "<v>"), ("<a>", "<q>", "<v>")]
    store = store_from_string_triples(triples)
    for compiled in (True, False):
        eng = QueryEngine(store, compiled=compiled)
        rows = eng.query(
            "SELECT ?x ?v WHERE { { ?x <p> ?v } UNION { ?x <q> ?v } }"
        )
        assert len(rows) == 2  # same solution from both branches survives


def test_union_warm_single_dispatch_zero_compiles():
    """Acceptance: warm-query dispatch count stays at 1 with 0 compiles
    for UNION queries."""
    store = student_store()
    eng = QueryEngine(store)
    pq = eng.prepare(UNION_QUERIES[0])
    cold = pq.run()
    assert cold.stats.cache_misses == 1 and cold.stats.n_compiles == 1
    warm = pq.run()
    assert warm.stats.n_dispatches == 1
    assert warm.stats.n_compiles == 0
    assert warm.stats.n_count_passes == 0


def test_filter_distributed_into_union_branches():
    store = student_store()
    text = PREFIX + """SELECT ?x ?v WHERE { ?x a ub:Student .
        { ?x ub:advisor ?v } UNION { ?x ub:name ?v }
        FILTER (?v != <p1>) }"""
    eng = QueryEngine(store)
    pq = eng.prepare(text)
    got = pq.run()
    want = reference_rows(store, parse(text))
    assert rows_as_sets(got.rows) == rows_as_sets(want)
    assert "distributed into 2 UNION branch(es)" in pq.explain()


def test_union_parse_errors():
    for bad in [
        "SELECT ?x WHERE { { ?x <p> ?y } }",  # braced group, no UNION
        "SELECT ?x WHERE { { ?x <p> ?y } UNION { } }",  # empty branch
        # two separate UNION blocks
        """SELECT ?x WHERE { { ?x <p> ?y } UNION { ?x <q> ?y } .
           { ?x <r> ?y } UNION { ?x <s> ?y } }""",
        # OPTIONAL + UNION combination
        """SELECT ?x WHERE { ?x <p> ?y .
           { ?x <q> ?z } UNION { ?x <r> ?z } OPTIONAL { ?x <s> ?w } }""",
        # nested UNION inside a branch
        "SELECT ?x WHERE { { { ?x <p> ?y } UNION { ?x <q> ?y } } UNION "
        "{ ?x <r> ?y } }",
    ]:
        with pytest.raises(ParseError):
            parse(bad)


# ------------------------------------------------------- FILTER && / ||


@pytest.mark.parametrize("compiled", [True, False])
@pytest.mark.parametrize("cond", [
    "?a >= 25 || ?a < 20",
    "?a > 20 && ?a < 25",
    '(?a >= 25 && ?n != "student8") || ?a = 18',
    '?n = "student3" || ?n = "student5"',
    "(?a < 20 || ?a > 30) && ?x != <s14>",
])
def test_boolean_connectives_match_oracle(compiled, cond):
    store = student_store()
    eng = QueryEngine(store, compiled=compiled)
    text = (PREFIX + "SELECT ?x ?a ?n WHERE { ?x ub:age ?a . "
            f"?x ub:name ?n . FILTER ({cond}) }}")
    got = eng.query(text)
    want = reference_rows(store, parse(text))
    assert rows_as_sets(got) == rows_as_sets(want), cond


def test_or_with_unbound_operand_keeps_true_side():
    """SPARQL: error || true is true. A row whose OPTIONAL var is unbound
    still passes when the other disjunct holds."""
    store = student_store(n_students=6, n_with_advisor=3)
    text = PREFIX + """SELECT ?x ?y ?a WHERE {
        ?x a ub:Student . ?x ub:age ?a .
        OPTIONAL { ?x ub:advisor ?y }
        FILTER (?y = <p0> || ?a >= 21) }"""
    for compiled in (True, False):
        eng = QueryEngine(store, compiled=compiled)
        got = eng.query(text)
        want = reference_rows(store, parse(text))
        assert rows_as_sets(got) == rows_as_sets(want)


def test_filters_with_or_share_compiled_program():
    store = student_store()
    eng = QueryEngine(store)
    text = (PREFIX + "SELECT ?x WHERE {{ ?x ub:age ?a . "
            "FILTER (?a < {lo} || ?a > {hi}) }}")
    r1 = eng.prepare(text.format(lo=20, hi=30)).run()
    r2 = eng.prepare(text.format(lo=19, hi=25)).run()
    assert r1.stats.cache_misses == 1
    assert r2.stats.cache_hits == 1 and r2.stats.n_compiles == 0
    want = reference_rows(store, parse(text.format(lo=19, hi=25)))
    assert rows_as_sets(r2.rows) == rows_as_sets(want)


# ----------------------------------------------- planner cross-join order


def test_plan_bgp_cross_joins_smallest_first():
    cards = {"<big>": 100.0, "<mid>": 20.0, "<tiny>": 5.0}
    patterns = [
        TriplePattern("?x", "<big>", "?y"),
        TriplePattern("?z", "<mid>", "?w"),
        TriplePattern("?u", "<tiny>", "?v"),
    ]
    steps = plan_bgp(patterns, lambda tp: cards[tp.p])
    assert [st.pattern_index for st in steps] == [2, 1, 0]
    assert [st.is_cross for st in steps] == [False, True, True]


# ------------------------------------------------- warmup persistence


def test_save_cache_warmup_skips_calibration(tmp_path):
    store = student_store()
    eng = QueryEngine(store)
    q1 = PREFIX + "SELECT ?x WHERE { ?x a ub:Student . ?x ub:age ?a . }"
    q2 = UNION_QUERIES[0]
    rows1 = eng.query(q1)
    rows2 = eng.query(q2)
    path = tmp_path / "warmup.json"
    assert eng.save_cache(str(path)) == 2
    # a fresh engine (fresh process stand-in) with the warmup file
    eng2 = QueryEngine(store, warmup_path=str(path))
    r1 = eng2.prepare(q1).run()
    # no calibration: zero count passes, exactly one compile + dispatch
    assert r1.stats.n_count_passes == 0
    assert r1.stats.n_compiles == 1
    assert r1.stats.n_dispatches == 1
    assert rows_as_sets(r1.rows) == rows_as_sets(rows1)
    r2 = eng2.prepare(q2).run()
    assert r2.stats.n_count_passes == 0
    assert rows_as_sets(r2.rows) == rows_as_sets(rows2)
    # from the second run on it is a plain cache hit
    r1b = eng2.prepare(q1).run()
    assert r1b.stats.cache_hits == 1 and r1b.stats.n_compiles == 0


def test_warmup_missing_file_is_fresh_start(tmp_path):
    store = student_store()
    eng = QueryEngine(store, warmup_path=str(tmp_path / "absent.json"))
    assert len(eng.query(PREFIX + "SELECT ?x WHERE { ?x a ub:Student . }")) \
        == 15


def test_shape_json_roundtrip():
    shape = plan_ir.make_shape(
        (("?c0", "?c1"), ("?c1", "?c2"), ("?c0", "?c3"), ("?c0", "?c4")),
        (16, 8, 8, 32),
        (False,),
        ("?c0", "?c2"),
        True,
        opt_groups=(),
        union_groups=(plan_ir.GroupSpec(1, ()), plan_ir.GroupSpec(1, ())),
        has_required=True,
        filters=(
            (("scan", 0), ("cmp", "?c1", ">", "num", 0)),
            (("top",), ("or", (("cmp", "?c0", "!=", "id", 0),
                               ("cmp", "?c0", "=", "var", "?c2")))),
        ),
        n_consts=(1, 1),
        has_slice=True,
        prune=True,
    )
    back = plan_ir.shape_from_jsonable(
        json.loads(json.dumps(plan_ir.shape_to_jsonable(shape)))
    )
    assert back == shape and hash(back) == hash(shape)


def test_server_save_cache_passthrough(tmp_path):
    from repro.serve.sparql_server import SPARQLServer

    store = student_store()
    srv = SPARQLServer(QueryEngine(store), max_batch=2)
    try:
        srv.query(PREFIX + "SELECT ?x WHERE { ?x a ub:Student . }")
        path = tmp_path / "server-warmup.json"
        assert srv.save_cache(str(path)) == 1
        assert path.exists()
    finally:
        srv.close()


# ---------------------------------------- property-based differential


def _mini_store(seed: int):
    rng = np.random.default_rng(seed)
    ents = [f"<e{i}>" for i in range(6)]
    triples = set()
    for _ in range(40):
        triples.add((
            ents[rng.integers(6)],
            f"<p{rng.integers(3)}>",
            ents[rng.integers(6)],
        ))
    for i in range(6):  # numeric attributes for FILTER coverage
        triples.add((ents[i], "<age>", str(15 + 3 * i)))
    return store_from_string_triples(sorted(triples))


def _query_text(shape: str, p1: int, p2: int, cmp_op: str, cut: int) -> str:
    """A query template per operator shape, always engine-valid."""
    base = f"?x <p{p1}> ?y"
    if shape == "bgp":
        return f"SELECT ?x ?y ?z WHERE {{ {base} . ?y <p{p2}> ?z . }}"
    if shape == "filter":
        return (f"SELECT ?x ?y ?a WHERE {{ {base} . ?x <age> ?a . "
                f"FILTER (?a {cmp_op} {cut} || ?x = <e1>) }}")
    if shape == "optional":
        return (f"SELECT ?x ?y ?z WHERE {{ {base} . "
                f"OPTIONAL {{ ?x <p{p2}> ?z }} }}")
    assert shape == "union"
    return (f"SELECT ?x ?v WHERE {{ {{ ?x <p{p1}> ?v }} UNION "
            f"{{ ?x <p{p2}> ?v }} }}")


@settings(max_examples=25, deadline=None)
@given(
    seed=st.integers(min_value=0, max_value=7),
    shape=st.sampled_from(["bgp", "filter", "optional", "union"]),
    p1=st.integers(min_value=0, max_value=2),
    p2=st.integers(min_value=0, max_value=2),
    cmp_op=st.sampled_from(["<", ">=", "=", "!="]),
    cut=st.integers(min_value=14, max_value=32),
)
def test_optimized_plan_matches_oracle_and_unoptimized(
    seed, shape, p1, p2, cmp_op, cut
):
    """Property (acceptance): every rewritten plan returns the same rows
    as baseline.reference_rows and as the unoptimized plan, across
    BGP/OPTIONAL/FILTER/UNION shapes."""
    store = _mini_store(seed)
    text = _query_text(shape, p1, p2, cmp_op, cut)
    q = parse(text)
    want = rows_as_sets(reference_rows(store, q))
    optimized = QueryEngine(store, compiled=False)
    unoptimized = QueryEngine(store, compiled=False, optimize=False)
    assert rows_as_sets(optimized.query(text)) == want, text
    assert rows_as_sets(unoptimized.query(text)) == want, text


@pytest.mark.parametrize("seed", [0, 3, 5])
@pytest.mark.parametrize("shape", ["bgp", "filter", "optional", "union"])
def test_differential_sweep_without_hypothesis(seed, shape):
    """Deterministic slice of the property-test space, so the differential
    guarantee is exercised even where hypothesis is unavailable."""
    store = _mini_store(seed)
    text = _query_text(shape, p1=seed % 3, p2=(seed + 1) % 3,
                       cmp_op="<" if seed % 2 else ">=", cut=18 + seed)
    q = parse(text)
    want = rows_as_sets(reference_rows(store, q))
    optimized = QueryEngine(store, compiled=False)
    unoptimized = QueryEngine(store, compiled=False, optimize=False)
    assert rows_as_sets(optimized.query(text)) == want, text
    assert rows_as_sets(unoptimized.query(text)) == want, text


@pytest.mark.parametrize("shape", ["bgp", "filter", "optional", "union"])
def test_compiled_matches_oracle_per_shape(shape):
    """The compiled (one-dispatch) pipeline agrees with the oracle on each
    operator shape the property test sweeps."""
    store = _mini_store(3)
    text = _query_text(shape, 0, 1, ">=", 21)
    q = parse(text)
    want = rows_as_sets(reference_rows(store, q))
    got = rows_as_sets(QueryEngine(store).query(text))
    assert got == want, text


# ------------------------------------------- dual physical join algebra


def _skew_store():
    """One hot object on <hot>: 40 subjects point at it; plus singletons."""
    triples = []
    for i in range(40):
        triples.append((f"<s{i}>", "<hot>", "<obj>"))
    for i in range(10):
        triples.append((f"<u{i}>", "<hot>", f"<v{i}>"))
        triples.append((f"<obj>", "<next>", f"<w{i}>"))
    return store_from_string_triples(triples)


def test_predicate_skew_statistics():
    stats = _skew_store().statistics
    by_name = {}
    lookup = _skew_store().dictionary  # only for readability below
    for pid, ps in stats.predicates.items():
        by_name[pid] = ps
    hot = max(stats.predicates.values(), key=lambda ps: ps.max_o_degree)
    assert hot.count == 50 and hot.max_o_degree == 40
    assert hot.o_skew == pytest.approx(40 / (50 / 11))
    assert hot.max_s_degree == 1 and hot.s_skew == pytest.approx(1.0)


def test_skew_statistics_json_roundtrip():
    stats = _skew_store().statistics
    back = StoreStatistics.from_jsonable(
        json.loads(json.dumps(stats.to_jsonable()))
    )
    assert back == stats
    # pre-skew catalogs (3-entry rows) default the degrees to uniform
    old = stats.to_jsonable()
    old["predicates"] = {
        pid: row[:3] for pid, row in old["predicates"].items()
    }
    degraded = StoreStatistics.from_jsonable(old)
    assert all(
        ps.max_s_degree == 1 and ps.max_o_degree == 1
        for ps in degraded.predicates.values()
    )


def test_optimizer_routes_skewed_join_to_matrix_backend():
    """S1's hot-key join must be routed to the matrix backend from the
    store statistics alone — no override — and the trace must say so."""
    store = lubm.generate(scale=1, seed=0, skew_shapes=True)
    eng = QueryEngine(store)
    text = lubm.S_QUERIES["S1"]
    prog = eng._build_program(eng.prepare(text).query)
    assert prog.plan.join_backends == ("matrix",)
    assert "matrix_join" in eng.explain(text)
    assert "join_backend[required]: matrix join" in eng.explain(text)


def test_uniform_joins_stay_on_mr_backend():
    """Plain LUBM joins have no hot key: every slot keeps the MR backend
    and explain() renders mr_join with its count method."""
    store = lubm.generate(scale=1, seed=0)
    eng = QueryEngine(store)
    for name in ("Q2", "Q9"):
        prog = eng._build_program(eng.prepare(lubm.QUERIES[name]).query)
        assert set(prog.plan.join_backends) <= {"mr"}, name
        text = eng.explain(lubm.QUERIES[name])
        assert "matrix_join" not in text
        joins = [ln for ln in text.splitlines() if ln.startswith("  join[")]
        assert joins and all("mr_join count=co-sort" in ln for ln in joins)


@pytest.mark.parametrize("n_left,n_right,want", [
    (64.0, 1024.0, "matrix"),  # 64*1024 + 1024^2 compares: under the cap
    (1.0, 4096.0, "mr"),  # the right side's 4096^2 rank pass is not
])
def test_matrix_rule_counts_the_right_rank_pass(n_left, n_right, want):
    """A hot-key join goes dense only while the layout grid PLUS the
    right side's |R|^2 rank pass fit MATRIX_DENSE_CAP, which also keeps
    the kernels' VMEM-resident right side small."""
    def state(card):
        return optimizer._State(card, {"?k": 1.0}, {"?k": 100.0}, ("?k",))

    got = optimizer._choose_backend(
        state(n_left), state(n_right), n_left * n_right)
    assert got == want


def test_join_backend_override_validation():
    store = student_store()
    with pytest.raises(ValueError, match="join_backend"):
        QueryEngine(store, join_backend="gpu")
    # valid values pass through to every join slot
    eng = QueryEngine(store, join_backend="matrix")
    q = PREFIX + "SELECT ?x ?a WHERE { ?x a ub:Student . ?x ub:age ?a . }"
    pq = eng.prepare(q)
    shape = eng._shape_for(
        pq._program,
        tuple(store.match_pattern(tp).schema for tp in pq._program.patterns),
        tuple(store.match_pattern(tp).capacity
              for tp in pq._program.patterns),
    )
    assert set(shape.join_backends) == {"matrix"}


def test_sharded_engine_accepts_matrix_backend():
    """The shard-local join is the single-device algebra verbatim, so the
    SpMM backend is valid inside shard_map too (it used to be pinned to
    "mr"); matrix results must match the mr backend on a sharded store."""
    from repro.sparql.engine import ShardedQueryEngine
    from repro.sparql.sharded_store import shard_store

    store = shard_store(student_store(), n_shards=1)
    q = PREFIX + "SELECT ?x ?a WHERE { ?x a ub:Student . ?x ub:age ?a . }"
    got_mr = rows_as_sets(ShardedQueryEngine(store, join_backend="mr").query(q))
    got_mx = rows_as_sets(
        ShardedQueryEngine(store, join_backend="matrix").query(q))
    assert got_mx == got_mr
    assert len(got_mx) > 0


@pytest.mark.parametrize("seed", [0, 2, 5])
@pytest.mark.parametrize("shape", ["bgp", "filter", "optional", "union"])
def test_backends_agree_with_oracle_per_shape(seed, shape):
    """Differential (acceptance): MR backend == matrix backend == NumPy
    oracle on every operator shape, compiled single-dispatch pipeline."""
    store = _mini_store(seed)
    text = _query_text(shape, p1=seed % 3, p2=(seed + 1) % 3,
                       cmp_op=">=" if seed % 2 else "<", cut=19 + seed)
    want = rows_as_sets(reference_rows(store, parse(text)))
    got_mr = rows_as_sets(QueryEngine(store, join_backend="mr").query(text))
    got_mx = rows_as_sets(
        QueryEngine(store, join_backend="matrix").query(text))
    assert got_mr == want, text
    assert got_mx == want, text


@settings(max_examples=20, deadline=None)
@given(
    seed=st.integers(min_value=0, max_value=7),
    shape=st.sampled_from(["bgp", "filter", "optional", "union"]),
    p1=st.integers(min_value=0, max_value=2),
    p2=st.integers(min_value=0, max_value=2),
)
def test_backends_agree_property(seed, shape, p1, p2):
    store = _mini_store(seed)
    text = _query_text(shape, p1, p2, ">=", 20)
    want = rows_as_sets(reference_rows(store, parse(text)))
    assert rows_as_sets(
        QueryEngine(store, join_backend="mr").query(text)) == want, text
    assert rows_as_sets(
        QueryEngine(store, join_backend="matrix").query(text)) == want, text


def test_matrix_backend_warm_single_dispatch():
    store = lubm.generate(scale=1, seed=0, skew_shapes=True)
    eng = QueryEngine(store)  # auto: picks matrix for S1 from stats
    pq = eng.prepare(lubm.S_QUERIES["S1"])
    pq.run()
    warm = pq.run()
    assert warm.stats.n_compiles == 0
    assert warm.stats.n_dispatches == 1
    assert len(warm.rows) == 20000


# -------------------------------------- filter-selectivity cost model


def _filter_order_store():
    """p1 is the biggest leaf (200 distinct subjects) but an `=` filter
    collapses it to ~1 row; blind ordering leads with the tiny p2-p3 tail
    instead (better sum of intermediates) and drags the full 200-row p1
    relation through the chain."""
    triples = []
    for i in range(200):
        triples.append((f"<x{i}>", "<p1>", f"<y{i % 4}>"))
    for i in range(4):
        triples.append((f"<y{i}>", "<p2>", f"<z{i}>"))
    for i in range(4):
        triples.append((f"<z{i}>", "<p3>", f"<w{i}>"))
    return store_from_string_triples(triples)


def test_filter_selectivity_changes_join_order():
    import dataclasses

    store = _filter_order_store()
    text = ("SELECT ?x ?y ?z ?w WHERE { ?x <p1> ?y . ?y <p2> ?z . "
            "?z <p3> ?w . FILTER (?x = <x3>) }")
    q = parse(text)
    aware = optimizer.optimize(q, store)
    blind = optimizer.optimize(dataclasses.replace(q, filters=()), store)
    # the equality filter collapses p1's leaf estimate, so the aware
    # order leads with it; blind ordering starts elsewhere
    assert aware.required[0].p == "<p1>"
    assert blind.required[0].p != "<p1>"
    assert max(aware.join_ests) * 4 <= max(blind.join_ests)


def test_filter_selectivity_shrinks_join_buckets():
    """End-to-end regression: with the selectivity-aware model the
    compiled pipeline's peak join bucket shrinks vs the legacy order
    (which both ignores filters and orders greedily)."""
    store = _filter_order_store()
    text = ("SELECT ?x ?y ?z ?w WHERE { ?x <p1> ?y . ?y <p2> ?z . "
            "?z <p3> ?w . FILTER (?x = <x3>) }")
    r_opt = QueryEngine(store).prepare(text).run()
    r_leg = QueryEngine(store, optimize=False).prepare(text).run()
    assert rows_as_sets(r_opt.rows) == rows_as_sets(r_leg.rows)
    assert r_opt.stats.peak_join_bucket < r_leg.stats.peak_join_bucket


# ------------------------------------------ warmup with skew statistics


def test_save_cache_v3_roundtrips_statistics_and_backends(tmp_path):
    store = lubm.generate(scale=1, seed=0, skew_shapes=True)
    eng = QueryEngine(store)
    text = lubm.S_QUERIES["S1"]
    eng.prepare(text).run()
    path = tmp_path / "warmup.json"
    assert eng.save_cache(str(path)) == 1
    blob = json.loads(path.read_text())
    assert blob["version"] == 3
    assert "statistics" in blob
    assert any(
        "matrix" in e["shape"].get("join_backends", [])
        for e in blob["entries"]
    )
    # a fresh engine on a fresh store object: statistics come from the
    # file (no recompute) and the matrix plan replays without calibration
    store2 = lubm.generate(scale=1, seed=0, skew_shapes=True)
    assert store2._statistics is None
    eng2 = QueryEngine(store2, warmup_path=str(path))
    assert store2._statistics is not None
    r = eng2.prepare(text).run()
    assert r.stats.n_count_passes == 0
    assert r.stats.n_compiles == 1 and r.stats.n_dispatches == 1
    assert len(r.rows) == 20000


def test_save_cache_v2_files_still_load(tmp_path):
    """Warmup files from before the statistics block (version 2) load;
    shapes without join_backends default every slot to the MR backend."""
    store = student_store()
    eng = QueryEngine(store)
    q = PREFIX + "SELECT ?x ?a WHERE { ?x a ub:Student . ?x ub:age ?a . }"
    eng.query(q)
    path = tmp_path / "v2.json"
    eng.save_cache(str(path))
    blob = json.loads(path.read_text())
    blob["version"] = 2
    blob.pop("statistics", None)
    for e in blob["entries"]:
        e["shape"].pop("join_backends", None)
    path.write_text(json.dumps(blob))
    eng2 = QueryEngine(store, warmup_path=str(path))
    r = eng2.prepare(q).run()
    assert r.stats.n_count_passes == 0
    assert rows_as_sets(r.rows) == rows_as_sets(eng.query(q))
