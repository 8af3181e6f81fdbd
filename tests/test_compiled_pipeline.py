"""The compiled query pipeline: plan IR, one-dispatch executor, plan/compile
cache — hit/miss accounting, bucket-overflow retry, compiled-vs-eager
differential results, device-side DISTINCT, and the `;` parser extension."""
import numpy as np
import pytest

from repro.core import plan_ir
from repro.sparql import lubm
from repro.sparql.engine import QueryEngine
from repro.sparql.parser import ParseError, parse
from repro.sparql.store import store_from_string_triples


@pytest.fixture(scope="module")
def lubm_store():
    return lubm.generate(scale=1, seed=0)


def rows_as_sets(rows):
    return sorted(tuple(sorted(r.items())) for r in rows)


# ---------------------------------------------------------------- bucketing


def test_bucket_capacity_quantizes_pow2_with_floor():
    assert plan_ir.bucket_capacity(0) == plan_ir.MIN_BUCKET
    assert plan_ir.bucket_capacity(1) == plan_ir.MIN_BUCKET
    assert plan_ir.bucket_capacity(8) == 8
    assert plan_ir.bucket_capacity(9) == 16
    assert plan_ir.bucket_capacity(1000) == 1024
    # near-miss sizes share a bucket -> share a compiled shape
    assert plan_ir.bucket_capacity(513) == plan_ir.bucket_capacity(1024)


def test_canonical_renaming_is_order_stable():
    m = plan_ir.canonical_renaming((("?b", "?a"), ("?a", "?z")))
    assert m == {"?b": "?c0", "?a": "?c1", "?z": "?c2"}


# ------------------------------------------------------- cache hit behaviour


def test_warm_cache_zero_compiles_single_dispatch(lubm_store):
    """Acceptance: a repeated LUBM query = 0 jit compiles, 1 device dispatch
    for the whole join chain, no per-join count passes, no retries."""
    eng = QueryEngine(lubm_store)
    for name, text in lubm.QUERIES.items():
        q = parse(text)
        _, cold = eng.execute(q)
        assert cold.cache_misses == 1 and cold.n_compiles == 1, name
        rel, warm = eng.execute(q)
        assert warm.cache_hits == 1, name
        assert warm.n_compiles == 0, name
        assert warm.n_dispatches == 1, name
        assert warm.n_count_passes == 0, name
        assert warm.n_retries == 0, name
        assert len(rel.to_numpy()) > 0, name


def test_cache_shared_across_variable_renames(lubm_store):
    """Same structure, different variable spelling -> same compiled plan."""
    eng = QueryEngine(lubm_store)
    q1 = lubm.PREFIX + """SELECT ?s ?p WHERE {
        ?s ub:advisor ?p . ?p ub:worksFor <http://example.org/Dept0_0> . }"""
    q2 = lubm.PREFIX + """SELECT ?student ?adv WHERE {
        ?student ub:advisor ?adv .
        ?adv ub:worksFor <http://example.org/Dept0_0> . }"""
    _, s1 = eng.execute(parse(q1))
    rel, s2 = eng.execute(parse(q2))
    assert s1.cache_misses == 1
    assert s2.cache_hits == 1 and s2.n_compiles == 0
    assert rel.schema == ("?student", "?adv")


def test_cache_miss_on_different_shape(lubm_store):
    eng = QueryEngine(lubm_store)
    _, s1 = eng.execute(parse(lubm.QUERIES["Q2"]))
    _, s2 = eng.execute(parse(lubm.QUERIES["Q4"]))
    assert s1.cache_misses == 1 and s2.cache_misses == 1
    assert len(eng.plan_cache) == 2


# ------------------------------------------------------- overflow -> retry


def test_bucket_overflow_grows_and_retries():
    """A same-shape query with a much larger join result overflows the
    cached bucket; the engine grows it from the exact totals and recompiles
    (the host-level Mars fallback), still returning exact results."""
    triples = [("<z>", "<p0>", "<w>")]
    triples += [(f"<h>", "<p0>", f"<v{i}>") for i in range(50)]
    triples += [("<z>", "<p1>", "<c1>"), ("<h>", "<p1>", "<c2>")]
    store = store_from_string_triples(triples)
    eng = QueryEngine(store)

    def q(const):
        return f"SELECT ?x ?y WHERE {{ ?x <p0> ?y . ?x <p1> <{const}> . }}"

    rows1 = eng.query(q("c1"))  # cold: calibrates tiny join bucket
    assert rows_as_sets(rows1) == rows_as_sets([{"?x": "<z>", "?y": "<w>"}])
    rel, stats = eng.execute(parse(q("c2")))  # warm hit, 50 results
    assert stats.cache_hits == 1
    assert stats.n_retries >= 1 and stats.n_compiles >= 1
    got = {tuple(int(x) for x in r) for r in rel.to_numpy()}
    eager = QueryEngine(store, compiled=False)
    want, _ = eager.execute(parse(q("c2")))
    assert got == want.to_set()
    assert len(got) == 50
    # the grown bucket is now cached: next time, no retry
    _, again = eng.execute(parse(q("c2")))
    assert again.n_retries == 0 and again.n_compiles == 0
    assert again.n_dispatches == 1


# ------------------------------------------- compiled vs eager differential


def test_compiled_matches_eager_on_lubm(lubm_store):
    compiled = QueryEngine(lubm_store)
    eager = QueryEngine(lubm_store, compiled=False)
    for name, text in lubm.QUERIES.items():
        for _ in range(2):  # cold then warm
            assert rows_as_sets(compiled.query(text)) == rows_as_sets(
                eager.query(text)
            ), name


def test_compiled_matches_eager_with_distinct(lubm_store):
    text = lubm.PREFIX + """SELECT DISTINCT ?d WHERE {
        ?s ub:memberOf ?d . ?s ub:advisor ?p . }"""
    compiled = QueryEngine(lubm_store)
    eager = QueryEngine(lubm_store, compiled=False)
    got_c = compiled.query(text)
    got_e = eager.query(text)
    assert rows_as_sets(got_c) == rows_as_sets(got_e)
    # dedup really happened (device-side, before decode)
    depts = [r["?d"] for r in got_c]
    assert len(depts) == len(set(depts)) == 15


def test_distinct_deduplicates_before_decode():
    triples = [
        ("<doctor>", "<workAt>", '"Hospital"'),
        ("<nurse>", "<workAt>", '"Hospital"'),
        ("<professor>", "<workAt>", '"University"'),
    ]
    for compiled in (True, False):
        eng = QueryEngine(store_from_string_triples(triples), compiled=compiled)
        q = parse('SELECT DISTINCT ?place WHERE { ?job <workAt> ?place . }')
        rel, _ = eng.execute(q)
        rows = rel.to_numpy()
        assert len(rows) == 2  # already unique on device
        assert sorted(r["?place"] for r in eng.query(
            'SELECT DISTINCT ?place WHERE { ?job <workAt> ?place . }'
        )) == ['"Hospital"', '"University"']


# --------------------------------------------------------- scans & serving


def test_device_scans_upload_once():
    store = lubm.generate(scale=1, seed=3)
    eng = QueryEngine(store)
    eng.query(lubm.QUERIES["Q4"])
    misses_after_cold = store.scan_cache_stats()["misses"]
    eng.query(lubm.QUERIES["Q4"])
    s = store.scan_cache_stats()
    assert s["misses"] == misses_after_cold  # no re-staging on the warm run
    assert s["hits"] >= 3  # one per pattern


def test_server_reports_cache_hit_rate():
    from repro.serve.sparql_server import SPARQLServer

    store = lubm.generate(scale=1, seed=2)
    srv = SPARQLServer(QueryEngine(store), max_batch=4)
    try:
        text = lubm.QUERIES["Q1"]
        for _ in range(4):
            srv.query(text)
        stats = srv.stats()
        assert stats["requests"] == 4
        assert stats["plan_cache"]["misses"] == 1
        assert stats["plan_cache"]["hits"] == 3
        assert stats["plan_cache"]["hit_rate"] == pytest.approx(0.75)
        assert stats["scan_cache"]["hits"] > 0
    finally:
        srv.close()


def test_server_survives_bad_query():
    from repro.serve.sparql_server import SPARQLServer

    store = store_from_string_triples([("<a>", "<p>", "<b>")])
    srv = SPARQLServer(QueryEngine(store), max_batch=2)
    try:
        with pytest.raises(ParseError):
            srv.query("SELECT garbage")
        # the worker thread survived; later requests still serve
        assert srv.query("SELECT ?x WHERE { ?x <p> <b> . }") == [
            {"?x": "<a>"}
        ]
    finally:
        srv.close()


# ------------------------------------------------------------------ parser


def test_parser_semicolon_predicate_object_list():
    q = parse(lubm.PREFIX + """SELECT ?x ?d WHERE {
        ?x a ub:GraduateStudent ; ub:memberOf ?d .
    }""")
    assert len(q.patterns) == 2
    assert q.patterns[0].s == q.patterns[1].s == "?x"
    assert q.patterns[0].p.endswith("rdf-syntax-ns#type>")
    assert q.patterns[1].o == "?d"


def test_parser_semicolon_executes_like_expanded_form(lubm_store):
    eng = QueryEngine(lubm_store)
    compact = lubm.PREFIX + """SELECT ?s ?d WHERE {
        ?s a ub:GraduateStudent ; ub:memberOf ?d ; ub:advisor ?p . }"""
    expanded = lubm.PREFIX + """SELECT ?s ?d WHERE {
        ?s a ub:GraduateStudent .
        ?s ub:memberOf ?d .
        ?s ub:advisor ?p . }"""
    assert rows_as_sets(eng.query(compact)) == rows_as_sets(
        eng.query(expanded)
    )


def test_parser_semicolon_trailing_and_errors():
    q = parse('SELECT ?x WHERE { ?x <p> <o> ; . }')  # dangling ; tolerated
    assert len(q.patterns) == 1
    with pytest.raises(ParseError):
        parse('SELECT ?x WHERE { ?x <p> ; <o> . }')  # ; needs a full p-o pair


# ------------------------------------------------- persistent compile cache
def test_compile_cache_env_dir_wins_else_fixed_checkout_path(monkeypatch):
    """Entry points keep jax's persistent cache where
    JAX_COMPILATION_CACHE_DIR says (setting nothing in code), else at
    <checkout>/.jax_cache — a fixed path, since it is part of the key."""
    import pathlib

    import jax

    from repro.launch import compile_cache as cc

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv(cc.ENV_VAR, "elsewhere")
        assert cc.enable_compile_cache() == "elsewhere"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv(cc.ENV_VAR)
        assert cc.enable_compile_cache() == str(cc.DEFAULT_DIR)
        assert jax.config.jax_compilation_cache_dir == str(cc.DEFAULT_DIR)
        checkout = pathlib.Path(__file__).resolve().parents[1]
        assert cc.DEFAULT_DIR == checkout / ".jax_cache"
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
