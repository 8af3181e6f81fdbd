"""Subprocess body: sharded SPARQL execution on N forced host devices.

Differential acceptance for the sharded subsystem at a real device count
(the parent pytest process keeps 1 device — XLA locks the count at first
jax import):

  * every LUBM bench query (plus FILTER / OPTIONAL / UNION / LIMIT
    operator shapes) answers IDENTICALLY through the sharded engine, the
    single-device engine and the NumPy oracle;
  * a deterministic slice of the property-test query space (the same
    generator tests/test_sharded.py sweeps under hypothesis at 1 device)
    agrees with the oracle too;
  * warm queries are exactly ONE shard_map dispatch with ZERO compiles;
  * the per-shard max join bucket never exceeds the single-device bucket,
    and is strictly smaller on the join-heavy queries when n_dev > 1.

Usage: sharded_query_prog.py [n_devices]   (default 8)
"""
import os
import sys

N_DEV = int(sys.argv[1]) if len(sys.argv) > 1 else 8
os.environ["XLA_FLAGS"] = (
    f"--xla_force_host_platform_device_count={N_DEV} "
    + os.environ.get("XLA_FLAGS", "")
)

import jax  # noqa: E402

from repro.core import compat  # noqa: E402
from repro.sparql import lubm  # noqa: E402
from repro.sparql.baseline import reference_rows  # noqa: E402
from repro.sparql.engine import QueryEngine, ShardedQueryEngine  # noqa: E402
from repro.sparql.parser import parse  # noqa: E402
from repro.sparql.sharded_store import shard_store  # noqa: E402
from repro.sparql.store import store_from_string_triples  # noqa: E402

EXTRA = {
    "F1": lubm.PREFIX + """SELECT ?p ?n WHERE {
        ?p a ub:FullProfessor . ?p ub:name ?n .
        FILTER (?n != "prof_0_0_0") }""",
    "O1": lubm.PREFIX + """SELECT ?s ?a WHERE {
        ?s a ub:GraduateStudent . OPTIONAL { ?s ub:advisor ?a } }""",
    "U1": lubm.PREFIX + """SELECT ?s ?v WHERE {
        ?s a ub:GraduateStudent .
        { ?s ub:advisor ?v } UNION { ?s ub:memberOf ?v } }""",
    "D1q": lubm.PREFIX + "SELECT DISTINCT ?d WHERE { ?s ub:memberOf ?d . }",
    "L1": lubm.PREFIX
    + "SELECT ?s ?d WHERE { ?s ub:memberOf ?d . } LIMIT 17",
}


def rows_key(rows):
    return sorted(tuple(sorted(r.items())) for r in rows)


def sweep_store(seed):
    """The mini random store the in-process property test uses."""
    import numpy as np

    rng = np.random.default_rng(seed)
    ents = [f"<e{i}>" for i in range(6)]
    triples = set()
    for _ in range(40):
        triples.add((
            ents[rng.integers(6)],
            f"<p{rng.integers(3)}>",
            ents[rng.integers(6)],
        ))
    for i in range(6):
        triples.add((ents[i], "<age>", str(15 + 3 * i)))
    return sorted(triples)


def sweep_query(shape, p1, p2, cmp_op, cut):
    base = f"?x <p{p1}> ?y"
    if shape == "bgp":
        return f"SELECT ?x ?y ?z WHERE {{ {base} . ?y <p{p2}> ?z . }}"
    if shape == "filter":
        return (f"SELECT ?x ?y ?a WHERE {{ {base} . ?x <age> ?a . "
                f"FILTER (?a {cmp_op} {cut} || ?x = <e1>) }}")
    if shape == "optional":
        return (f"SELECT ?x ?y ?z WHERE {{ {base} . "
                f"OPTIONAL {{ ?x <p{p2}> ?z }} }}")
    return (f"SELECT ?x ?v WHERE {{ {{ ?x <p{p1}> ?v }} UNION "
            f"{{ ?x <p{p2}> ?v }} }}")


def main():
    assert jax.device_count() == N_DEV, (jax.device_count(), N_DEV)
    store = lubm.generate(scale=1, seed=0, join_shapes=True)
    single = QueryEngine(store)
    sharded = ShardedQueryEngine(shard_store(store, N_DEV))
    queries = {**lubm.QUERIES, **lubm.J_QUERIES, **EXTRA}
    bucket_wins = 0
    for name, text in queries.items():
        pq_single = single.prepare(text)
        pq_sharded = sharded.prepare(text)
        rows_single = pq_single.run()
        rows_sharded = pq_sharded.run()
        if name == "L1":  # any right-sized subset is a correct slice
            want = rows_key(reference_rows(store, parse(text)))
            assert len(rows_single) == len(rows_sharded) == 17
            assert set(rows_key(rows_sharded.rows)) <= set(want), name
        else:
            want = rows_key(reference_rows(store, parse(text)))
            assert rows_key(rows_single.rows) == want, name
            assert rows_key(rows_sharded.rows) == want, (
                name, len(rows_sharded), len(want))
        # warm: one shard_map dispatch, zero compiles, for both engines
        warm_sh = pq_sharded.run()
        assert warm_sh.stats.n_dispatches == 1, (name, warm_sh.stats)
        assert warm_sh.stats.n_compiles == 0, (name, warm_sh.stats)
        warm_si = pq_single.run()
        # per-shard bucket accounting vs the single-device bucket
        sh_b = warm_sh.stats.peak_join_bucket
        si_b = warm_si.stats.peak_join_bucket
        assert sh_b <= si_b, (name, sh_b, si_b)
        if sh_b < si_b:
            bucket_wins += 1
        print(f"ok {name}: rows={len(rows_sharded)} "
              f"per_shard_bucket={sh_b} single_bucket={si_b}")
    if N_DEV > 1:
        assert bucket_wins > 0, "sharding never shrank a join bucket"
    if N_DEV == 8:
        # hierarchical 2x4 (pod x data) mesh: the two-stage shuffle routes
        # inter-pod first, then intra-pod — results must stay identical
        mesh2 = compat.make_mesh((2, 4), ("pod", "data"))
        hier = ShardedQueryEngine(shard_store(store, 8), mesh=mesh2)
        for name in ("Q2", "Q9", "U1"):
            text = queries[name]
            want = rows_key(reference_rows(store, parse(text)))
            assert rows_key(hier.query(text)) == want, ("2x4", name)
        print("ok hierarchical 2x4 mesh")
    # deterministic slice of the property-test space
    for seed in (0, 3, 5):
        triples = sweep_store(seed)
        st = store_from_string_triples(triples)
        s_eng = ShardedQueryEngine(shard_store(st, N_DEV))
        for shape in ("bgp", "filter", "optional", "union"):
            text = sweep_query(shape, seed % 3, (seed + 1) % 3,
                               "<" if seed % 2 else ">=", 18 + seed)
            want = rows_key(reference_rows(st, parse(text)))
            got = rows_key(s_eng.query(text))
            assert got == want, (seed, shape, text)
        print(f"ok sweep seed={seed}")
    check_shuffle_elision(store, sharded)
    check_broadcast_join()
    check_stacked_batch()
    print(f"ALL SHARDED QUERY CASES PASSED n_dev={N_DEV}")


def check_shuffle_elision(store, sharded):
    """Partitioning-aware lowering at a real device count: the subject-
    star emits ZERO shuffle collectives (both scans born subject-hash
    aligned on the join key), the chain emits exactly one per join (the
    probe side arrives partitioned on the previous key)."""
    star = lubm.PREFIX + """SELECT ?s ?a WHERE {
        ?s a ub:GraduateStudent . ?s ub:advisor ?a . }"""
    pq = sharded.prepare(star)
    want = rows_key(reference_rows(store, parse(star)))
    assert rows_key(pq.run().rows) == want
    warm = pq.run()
    assert warm.stats.n_shuffles_emitted == 0, warm.stats
    assert warm.stats.n_shuffles_elided == 2, warm.stats
    chain = lubm.PREFIX + """SELECT ?s ?n WHERE {
        ?s ub:advisor ?p . ?p ub:name ?n . }"""
    pq = sharded.prepare(chain)
    want = rows_key(reference_rows(store, parse(chain)))
    assert rows_key(pq.run().rows) == want
    warm = pq.run()
    if N_DEV > 1:
        assert warm.stats.n_shuffles_emitted == 1, warm.stats
        assert warm.stats.n_shuffles_elided == 1, warm.stats
    else:  # 1 shard: everything is trivially aligned
        assert warm.stats.n_shuffles_emitted == 0, warm.stats
    print("ok shuffle elision (star=0 emitted, chain=1 emitted)")


def check_broadcast_join():
    """Both join inputs misaligned on an object-object key + a small
    build side: the lowering replicates the small side with ONE
    all_gather instead of shuffling both — and the answer still matches
    the oracle."""
    st = store_from_string_triples(sweep_store(0))
    eng = ShardedQueryEngine(shard_store(st, N_DEV))
    text = "SELECT ?x ?y ?z WHERE { ?x <p0> ?y . ?z <p1> ?y . }"
    want = rows_key(reference_rows(st, parse(text)))
    pq = eng.prepare(text)
    assert rows_key(pq.run().rows) == want
    warm = pq.run()
    if N_DEV > 1:
        assert warm.stats.n_broadcast_joins == 1, warm.stats
        assert warm.stats.n_shuffles_emitted == 0, warm.stats
    print("ok broadcast join")


def check_stacked_batch():
    """Warm same-shape queries (different runtime constants) ride ONE
    stacked (lanes x shards) dispatch on the real mesh."""
    st = store_from_string_triples(sweep_store(3))
    eng = ShardedQueryEngine(shard_store(st, N_DEV))
    texts = [sweep_query("filter", 0, 1, ">=", cut) for cut in (16, 19, 25)]
    eng.query(texts[0])  # warm the shape
    prepared = [eng.prepare(t) for t in texts]
    out = eng.run_batch(prepared)
    for t, rs in zip(texts, out):
        assert rows_key(rs.rows) == rows_key(
            reference_rows(st, parse(t))), t
    group = eng.last_batch[0]
    assert not group.fallback, "stacked sharded dispatch fell back"
    assert group.widths == (4,), group
    assert group.n_dispatches == 1, group
    print("ok stacked batch")


if __name__ == "__main__":
    main()
