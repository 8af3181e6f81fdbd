"""Repeated-query throughput: eager per-join loop vs the compiled pipeline.

The eager engine pays, per join and per query, a jitted COUNT dispatch, a
host sync of the cardinality, and a jitted EXPAND dispatch (with a possible
recompile when the pow-2 capacity is new). The compiled pipeline pays
calibration + compilation ONCE per plan shape, then serves every repeat
with a single device dispatch from the plan/compile cache — the behaviour a
query-serving deployment actually sees.

Besides the 5 plain-BGP LUBM queries this also tracks the FILTER /
OPTIONAL / LIMIT / UNION operator shapes (F1, O1, FO1, U1) and the
bad-join-order shapes J1/J2, on which it additionally compares the
statistics-driven join order against the legacy greedy order and FAILS
(non-zero exit) if the optimizer stops producing strictly smaller maximum
join buckets — so planner regressions that explode intermediate sizes
fail the CI build (the bench-smoke job runs `--quick` on CPU).

B1/B2 measure batched same-shape execution: 16 / 64 warm queries of one
plan shape (differing only in a FILTER constant), run sequentially (N
dispatches) vs through engine.run_batch (ceil(N / width) stacked
dispatches). The dispatch count is asserted — it is the structural win and
is deterministic — and the timing ratio is reported; the batched records
are also written to the BENCH_4.json artifact.

W1 measures the live-update path: insert_triples ingest rate over a batch
size sweep, warm-query latency before / after in-headroom writes / after
compaction, and asserts the warm plan cache survives the whole sequence
(0 compiles, 1 dispatch) with results equal to a store rebuilt from
scratch. Records land in BENCH_7.json.

    PYTHONPATH=src python -m benchmarks.bench_query [scale] [repeats]
    PYTHONPATH=src python -m benchmarks.bench_query --quick
"""
from __future__ import annotations

import json
import os
import sys
import time

import jax

from repro.core import compat
from repro.launch.compile_cache import enable_compile_cache
from repro.sparql import lubm
from repro.sparql.engine import QueryEngine, ShardedQueryEngine
from repro.sparql.sharded_store import shard_store

# operator-coverage shapes: device-side FILTER masks, OPTIONAL left joins
# with UNBOUND padding, a LIMIT slice, and a UNION concat
EXTRA_QUERIES: dict[str, str] = {
    # F1: star BGP + string-identity and numeric-free filter
    "F1": lubm.PREFIX + """SELECT ?p ?n WHERE {
        ?p a ub:FullProfessor .
        ?p ub:name ?n .
        FILTER (?n != "prof_0_0_0")
    }""",
    # O1: wide type scan, optional advisor edge (some students unmatched)
    "O1": lubm.PREFIX + """SELECT ?s ?a WHERE {
        ?s a ub:GraduateStudent .
        OPTIONAL { ?s ub:advisor ?a }
    }""",
    # FO1: filter + optional + limit through one compiled program
    "FO1": lubm.PREFIX + """SELECT ?s ?d ?a WHERE {
        ?s ub:memberOf ?d .
        OPTIONAL { ?s ub:advisor ?a }
        FILTER (?s != ?a)
    } LIMIT 64""",
    # U1: shared required scan, two union branches, one compiled dispatch
    "U1": lubm.PREFIX + """SELECT ?s ?v WHERE {
        ?s a ub:GraduateStudent .
        { ?s ub:advisor ?v } UNION { ?s ub:memberOf ?v }
    }""",
}


def _time(fn, repeat: int) -> float:
    t0 = time.perf_counter()
    for _ in range(repeat):
        fn()
    return (time.perf_counter() - t0) / repeat


# batched same-shape serving shapes: N queries of ONE plan shape, differing
# only in a FILTER constant (a runtime input — all share the compiled plan)
B_SHAPES = {"B1": 16, "B2": 64}


def _b_queries(n: int) -> list[str]:
    return [
        lubm.PREFIX + f"""SELECT ?p ?n WHERE {{
            ?p a ub:FullProfessor .
            ?p ub:name ?n .
            FILTER (?n != "prof_0_{k % 8}_{k // 8}")
        }}"""
        for k in range(n)
    ]


def bench_batched(store, repeats: int) -> list[dict]:
    """Sequential vs stacked execution of N warm same-shape queries.

    Asserts the dispatch count (ceil(N / width) — the deterministic
    structural win) and reports the wall-clock throughput ratio.
    """
    out = []
    for name, n in B_SHAPES.items():
        eng = QueryEngine(store)
        prepared = [eng.prepare(t) for t in _b_queries(n)]
        seq = [pq.run() for pq in prepared]  # warm plan cache (1 calib)
        stacked = eng.run_batch(prepared)  # warm stacked width
        assert [r.rows for r in stacked] == [r.rows for r in seq], name
        t_seq = _time(lambda: [pq.run() for pq in prepared], repeats)
        t_bat = _time(lambda: eng.run_batch(prepared), repeats)
        group = eng.last_batch[0]
        width = max(group.widths)
        want = -(-n // width)  # ceil
        assert group.n_dispatches == want, (
            f"{name}: {n} warm same-shape queries took "
            f"{group.n_dispatches} stacked dispatches, want {want}"
        )
        out.append({
            "query": name,
            "n_queries": n,
            "rows": len(seq[0]),
            "batch_width": width,
            "stacked_dispatches": group.n_dispatches,
            "sequential_ms": t_seq * 1e3,
            "stacked_ms": t_bat * 1e3,
            "throughput_x": t_seq / t_bat,
        })
    return out


# sharded-vs-single device counts for the D1 shape (1 = the no-sharding
# baseline, 4 = the scaling point). Both meshes are built in THIS process
# from jax.devices(): on the chip one process holds every device; on the
# CPU, main() forces 4 host devices before jax initialises.
D1_DEVICE_COUNTS = (1, 4)
# D1: join-heavy shapes (the per-shard bucket-shrink claim)
D1_QUERIES = ("Q2", "Q7", "Q9", "J1")
# D2: subject-star shapes — every join key is the shared subject variable,
# so the subject-hash partitioned scans are ALREADY aligned and the
# lowering elides every shuffle (0 emitted collectives, asserted below)
STAR_QUERIES = ("Q1", "Q4")
# the 4-device wall-time win needs enough data for the smaller per-shard
# sorts to amortise the mesh dispatch overhead (on a single-core host the
# whole win IS the O(n log^2 n) bitonic work reduction); below this scale
# the D2 assert is skipped and only the structural claims are checked
D2_WALL_WIN_MIN_SCALE = 8


def _best_of(fn, repeat: int) -> float:
    """Best-of-repeat wall time: the min is the noise-robust statistic on
    a shared host (a load spike inflates the mean but not the min)."""
    best = float("inf")
    for _ in range(repeat):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def _sharded_records(store, n_dev: int, repeats: int) -> list[dict]:
    """Warm per-query latency of the single-device engine and a sharded
    engine over the first `n_dev` devices, plus bucket and shuffle
    counts, for every D-series query."""
    if jax.device_count() < n_dev:
        raise RuntimeError(
            f"the D-series needs {n_dev} devices, jax sees "
            f"{jax.device_count()}"
        )
    mesh = compat.make_mesh((n_dev,), ("shards",),
                            devices=jax.devices()[:n_dev])
    single = QueryEngine(store)
    sharded = ShardedQueryEngine(shard_store(store, n_dev), mesh=mesh)
    queries = {**lubm.QUERIES, **lubm.J_QUERIES}
    records = []
    for name in D1_QUERIES + STAR_QUERIES:
        pq_si = single.prepare(queries[name])
        pq_sh = sharded.prepare(queries[name])
        rows_si, rows_sh = pq_si.run(), pq_sh.run()
        assert len(rows_si) == len(rows_sh), (name, len(rows_si),
                                              len(rows_sh))
        warm_si, warm_sh = pq_si.run(), pq_sh.run()
        assert warm_sh.stats.n_dispatches == 1 and (
            warm_sh.stats.n_compiles == 0
        ), (name, warm_sh.stats)
        records.append({
            "query": name,
            "rows": len(rows_sh),
            "single_ms": _best_of(pq_si.run, repeats) * 1e3,
            "sharded_ms": _best_of(pq_sh.run, repeats) * 1e3,
            "single_max_bucket": warm_si.stats.peak_join_bucket,
            "per_shard_max_bucket": warm_sh.stats.peak_join_bucket,
            "shuffles_emitted": warm_sh.stats.n_shuffles_emitted,
            "shuffles_elided": warm_sh.stats.n_shuffles_elided,
            "broadcast_joins": warm_sh.stats.n_broadcast_joins,
        })
    return records


def bench_sharded(scale: int, repeats: int) -> list[dict]:
    """D1 + D2: the sharded engine vs the single-device engine on the
    LUBM join-heavy (D1) and subject-star (D2) queries, on 1 and 4
    devices.

    Asserts the structural wins at 4 devices so a sharding regression
    fails the bench (and the distributed-smoke CI job running it):

      * D1 — per-shard max join bucket strictly below the single-device
        bucket on the join-heavy queries;
      * D2 — the subject-star queries emit ZERO shuffle collectives (the
        partitioning-aware lowering proves both join inputs co-located),
        and at least two D-series queries run FASTER on the 4-device mesh
        than on the 1-device mesh (map-side joins + collective/compute
        overlap turn the shard count into wall-clock, not just memory).
    """
    store = lubm.generate(scale=scale, seed=0, join_shapes=True)
    by_dev = {
        n_dev: _sharded_records(store, n_dev, repeats)
        for n_dev in D1_DEVICE_COUNTS
    }
    d1_set = set(D1_QUERIES)
    out = []
    wall_wins = []
    for rec1, rec4 in zip(*(by_dev[d] for d in D1_DEVICE_COUNTS)):
        assert rec1["query"] == rec4["query"]
        name = rec4["query"]
        if name in d1_set:
            assert (
                rec4["per_shard_max_bucket"] < rec4["single_max_bucket"]
            ), (
                f"D1 {name}: per-shard bucket "
                f"{rec4['per_shard_max_bucket']} not below single-device "
                f"{rec4['single_max_bucket']}"
            )
        else:  # D2 subject-star: zero emitted collectives on the mesh
            assert rec4["shuffles_emitted"] == 0, (
                f"D2 {name}: emitted {rec4['shuffles_emitted']} shuffles"
            )
        if rec4["sharded_ms"] < rec1["sharded_ms"]:
            wall_wins.append(name)
        tag = "D1" if name in d1_set else "D2"
        out.append({
            "query": f"{tag}-{name}",
            "rows": rec4["rows"],
            "sharded_1dev_ms": rec1["sharded_ms"],
            "sharded_4dev_ms": rec4["sharded_ms"],
            "single_ms": rec4["single_ms"],
            "single_max_bucket": rec4["single_max_bucket"],
            "per_shard_max_bucket": rec4["per_shard_max_bucket"],
            "shuffles_emitted": rec4["shuffles_emitted"],
            "shuffles_elided": rec4["shuffles_elided"],
            "broadcast_joins": rec4["broadcast_joins"],
        })
    if scale >= D2_WALL_WIN_MIN_SCALE:
        assert len(wall_wins) >= 2, (
            f"D2: only {wall_wins} ran faster at 4 devices than at 1 "
            f"(need >= 2 of the D-series at scale {scale})"
        )
    else:
        print(f"# D2 wall-time assert skipped (scale {scale} < "
              f"{D2_WALL_WIN_MIN_SCALE}); wins so far: {wall_wins}")
    return out


def bench_optimizer(store) -> list[dict]:
    """Greedy vs statistics-driven join order on the J1/J2 shapes.

    Asserts the optimizer win (strictly smaller max join bucket, same
    rows) so a planner regression turns the benchmark red.
    """
    out = []
    for name, text in lubm.J_QUERIES.items():
        greedy = QueryEngine(store, optimize=False)
        stats = QueryEngine(store)
        pg = greedy.prepare(text)
        rows_g = pg.run()
        ps = stats.prepare(text)
        rows_s = ps.run()
        assert len(rows_g) == len(rows_s), name
        assert rows_s.stats.peak_join_bucket < rows_g.stats.peak_join_bucket, (
            f"{name}: optimizer no longer shrinks the max join bucket "
            f"({rows_s.stats.peak_join_bucket} vs "
            f"{rows_g.stats.peak_join_bucket})"
        )
        t_g = _time(lambda: pg.run(), 3)
        t_s = _time(lambda: ps.run(), 3)
        out.append({
            "query": f"{name}-joinorder",
            "rows": len(rows_s),
            "greedy_max_bucket": rows_g.stats.peak_join_bucket,
            "stats_max_bucket": rows_s.stats.peak_join_bucket,
            "greedy_ms": t_g * 1e3,
            "stats_ms": t_s * 1e3,
        })
    return out


def bench_backend(repeats: int, seed: int = 0) -> list[dict]:
    """S1: MR vs matrix join backend on the skewed-predicate shape.

    Both engines execute the SAME plan (same join order, same buckets) —
    only the physical join algebra differs. Asserts that the cost-based
    optimizer routes S1's hot-key join to the matrix backend from the
    statistics alone (no override), that both backends return identical
    rows, and reports the warm DEVICE-side timing of each: S1 returns
    20k rows, and decoding them to host dicts costs the same for both
    backends while dwarfing the join itself, so the timed section is the
    compiled dispatch up to block_until_ready, not the decode.
    """
    from repro.sparql.engine import ExecStats

    store = lubm.generate(scale=1, seed=seed, skew_shapes=True)
    out = []
    for name, text in lubm.S_QUERIES.items():
        auto = QueryEngine(store)
        chosen = auto._build_program(
            auto.prepare(text).query
        ).plan.join_backends
        assert "matrix" in chosen, (
            f"{name}: optimizer chose {chosen}, expected the matrix "
            "backend from selectivity x skew statistics"
        )
        mr = QueryEngine(store, join_backend="mr")
        mx = QueryEngine(store, join_backend="matrix")
        p_mr, p_mx = mr.prepare(text), mx.prepare(text)
        rows_mr, rows_mx = p_mr.run(), p_mx.run()
        key = lambda rs: sorted(
            tuple(sorted(d.items())) for d in rs.rows
        )
        assert key(rows_mr) == key(rows_mx), f"{name}: backend mismatch"
        warm = p_mx.run()
        assert warm.stats.n_compiles == 0 and warm.stats.n_dispatches == 1

        def device_run(engine, prepared):
            rel = engine._execute_program(prepared._program, ExecStats())
            rel.cols.block_until_ready()

        t_mr = _time(lambda: device_run(mr, p_mr), repeats)
        t_mx = _time(lambda: device_run(mx, p_mx), repeats)
        out.append({
            "query": f"{name}-backend",
            "rows": len(rows_mx),
            "chosen_backend": "matrix",
            "mr_ms": t_mr * 1e3,
            "matrix_ms": t_mx * 1e3,
            "matrix_speedup": t_mr / t_mx,
        })
    return out


def bench_updates(scale: int, repeats: int, seed: int = 0) -> dict:
    """W1: the live-update path — ingest rate, warm-query latency across
    writes, and compaction.

    Sweeps insert_triples batch sizes for triples/sec, then warms the F1
    filter shape, applies inserts sized within the warm pattern's bucket
    headroom (reusing existing dictionary terms, so neither the scan
    buckets nor the pow-2 numeric table change shape) plus a few deletes
    of original base rows, and measures warm latency before the writes,
    after the writes, and after compact(). Asserts the acceptance
    property: the previously-warm shape re-runs at 0 compiles / 1
    dispatch after writes AND after compaction, and its rows equal a
    store rebuilt from scratch from the post-update triples.
    """
    from repro.core.planner import TriplePattern
    from repro.sparql.store import store_from_string_triples

    store = lubm.generate(scale=scale, seed=seed)

    # ingest-rate sweep: fresh subjects/objects under a bench-only
    # predicate, so the query shapes below are untouched
    ingest = []
    k = 0
    for batch in (64, 256, 1024):
        rows = []
        for _ in range(batch):
            rows.append((f"<w1:s{k}>", "<w1:ingest>", f"<w1:o{k}>"))
            k += 1
        t0 = time.perf_counter()
        applied = store.insert_triples(rows)
        dt = time.perf_counter() - t0
        assert applied == batch
        ingest.append({
            "batch_size": batch,
            "ms": dt * 1e3,
            "triples_per_s": batch / dt,
        })

    eng = QueryEngine(store)
    text = EXTRA_QUERIES["F1"]
    pq = eng.prepare(text)
    pq.run()  # calibrate + compile
    warm0 = pq.run()
    assert warm0.stats.n_compiles == 0 and warm0.stats.n_dispatches == 1
    t_before = _time(lambda: pq.run(), repeats)

    # writes sized within the warm name-pattern's bucket headroom, built
    # from existing terms only (cross-pairing professors with other
    # professors' names) so no dictionary growth can force a recompile
    d = store.dictionary
    name_tp = TriplePattern("?p", f"<{lubm.UB}name>", "?n")
    matches = store.match_rows(name_tp)
    headroom = store.scan_capacity(name_tp) - len(matches)
    have = {(int(s), int(o)) for s, _, o in matches}
    pid = d.lookup(f"<{lubm.UB}name>")
    new_rows = []
    for s, _, _ in matches:
        o = int(matches[(len(new_rows) * 7 + 3) % len(matches)][2])
        if (int(s), o) not in have and len(new_rows) < max(0, headroom - 2):
            new_rows.append(
                (d.decode(int(s)), d.decode(pid), d.decode(o)))
            have.add((int(s), o))
    inserted = store.insert_triples(new_rows)
    deleted = store.delete_triples([
        (d.decode(int(s)), d.decode(int(p)), d.decode(int(o)))
        for s, p, o in matches[:2]
    ])
    warm1 = pq.run()
    assert warm1.stats.n_compiles == 0 and warm1.stats.n_dispatches == 1, (
        "W1: warm shape recompiled after in-headroom writes "
        f"({warm1.stats.n_compiles} compiles)"
    )
    t_after_writes = _time(lambda: pq.run(), repeats)
    ws_before_compact = store.write_stats()

    t0 = time.perf_counter()
    store.compact()
    compact_ms = (time.perf_counter() - t0) * 1e3
    warm2 = pq.run()
    assert warm2.stats.n_compiles == 0 and warm2.stats.n_dispatches == 1, (
        "W1: warm shape recompiled after compaction "
        f"({warm2.stats.n_compiles} compiles)"
    )
    t_after_compact = _time(lambda: pq.run(), repeats)

    # differential acceptance: post-update rows == a store rebuilt from
    # scratch from the effective triples
    rebuilt = store_from_string_triples(sorted(
        (d.decode(int(s)), d.decode(int(p)), d.decode(int(o)))
        for s, p, o in store.triples
    ))
    key = lambda rows: sorted(tuple(sorted(r.items())) for r in rows)
    assert key(warm2.rows) == key(QueryEngine(rebuilt).query(text)), (
        "W1: post-update results diverge from a rebuilt store"
    )

    return {
        "query": "W1",
        "rows": len(warm2.rows),
        "ingest": ingest,
        "inserted": inserted,
        "deleted": deleted,
        "warm_ms_before_writes": t_before * 1e3,
        "warm_ms_after_writes": t_after_writes * 1e3,
        "warm_ms_after_compact": t_after_compact * 1e3,
        "compact_ms": compact_ms,
        "write_stats_before_compact": ws_before_compact,
        "write_stats_after_compact": store.write_stats(),
        "warm_cache_preserved": True,  # asserted above
    }


def bench(scale: int = 2, repeats: int = 20, seed: int = 0) -> list[dict]:
    store = lubm.generate(scale=scale, seed=seed, join_shapes=True)
    eager = QueryEngine(store, compiled=False)
    compiled = QueryEngine(store)
    out = []
    queries = {**lubm.QUERIES, **EXTRA_QUERIES, **lubm.J_QUERIES}
    for name, text in queries.items():
        # warm both: the eager jit cache and the compiled plan cache
        rows_e = eager.query(text)
        rows_c = compiled.query(text)
        assert len(rows_e) == len(rows_c), name
        t_eager = _time(lambda: eager.query(text), repeats)
        t_compiled = _time(lambda: compiled.query(text), repeats)
        out.append({
            "query": name,
            "rows": len(rows_c),
            "eager_ms": t_eager * 1e3,
            "compiled_ms": t_compiled * 1e3,
            "speedup": t_eager / t_compiled,
        })
    out.extend(bench_optimizer(store))
    out.extend(bench_batched(store, repeats))
    out.append({"plan_cache": compiled.cache_stats(),
                "scan_cache": store.scan_cache_stats()})
    return out


def force_host_devices(n: int) -> None:
    """Give the CPU backend `n` devices for the D-series meshes. Must run
    before jax initialises a backend; the flag changes nothing for an
    accelerator's devices."""
    flag = f"--xla_force_host_platform_device_count={n}"
    if flag not in os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            flag + " " + os.environ.get("XLA_FLAGS", "")
        ).strip()


def main() -> None:
    force_host_devices(max(D1_DEVICE_COUNTS))
    enable_compile_cache()
    args = [a for a in sys.argv[1:]]
    quick = "--quick" in args
    sharded_only = "--sharded-only" in args
    pos = [a for a in args if not a.startswith("--")]
    # --sharded-only runs at the D2 scale: big enough that the 4-device
    # mesh's smaller per-shard sorts beat the 1-device mesh on wall time
    scale = int(pos[0]) if pos else (
        1 if quick else 96 if sharded_only else 2
    )
    repeats = int(pos[1]) if len(pos) > 1 else (
        3 if quick else 5 if sharded_only else 20
    )
    sharded_records = []
    if not sharded_only:
        print(f"# repeated (warm) LUBM queries, scale={scale}, "
              f"{repeats} repeats: eager vs compiled one-dispatch pipeline")
        print("query,rows,eager_ms,compiled_ms,speedup")
        rows = bench(scale=scale, repeats=repeats)
        batched_records = []
        for r in rows:
            if "throughput_x" in r:
                batched_records.append(r)
                print(f"# {r['query']}: {r['n_queries']} same-shape warm "
                      f"queries, width={r['batch_width']}, "
                      f"stacked_dispatches={r['stacked_dispatches']}, "
                      f"sequential_ms={r['sequential_ms']:.2f} "
                      f"stacked_ms={r['stacked_ms']:.2f} "
                      f"throughput={r['throughput_x']:.2f}x")
            elif "speedup" in r:
                print(f"{r['query']},{r['rows']},{r['eager_ms']:.2f},"
                      f"{r['compiled_ms']:.2f},{r['speedup']:.2f}")
            elif "query" in r:
                print(f"# {r['query']}: rows={r['rows']} "
                      f"greedy_max_bucket={r['greedy_max_bucket']} "
                      f"stats_max_bucket={r['stats_max_bucket']} "
                      f"greedy_ms={r['greedy_ms']:.2f} "
                      f"stats_ms={r['stats_ms']:.2f}")
            else:
                print(f"# {r}")
        # batched-throughput artifact (CI uploads it; see .github/workflows)
        with open("BENCH_4.json", "w") as f:
            json.dump({"scale": scale, "repeats": repeats,
                       "batched": batched_records}, f, indent=2)
        print("# wrote BENCH_4.json")
        # S1: MR vs matrix physical join algebra on the skewed shape
        backend_records = bench_backend(repeats)
        for r in backend_records:
            print(f"# {r['query']}: rows={r['rows']} "
                  f"chosen={r['chosen_backend']} "
                  f"mr_ms={r['mr_ms']:.2f} matrix_ms={r['matrix_ms']:.2f} "
                  f"matrix_speedup={r['matrix_speedup']:.2f}x")
        with open("BENCH_6.json", "w") as f:
            json.dump({"repeats": repeats,
                       "backend": backend_records}, f, indent=2)
        print("# wrote BENCH_6.json")
        # W1: live updates — ingest rate, warm latency across writes and
        # compaction, warm-cache-preserved + differential assertions
        w1 = bench_updates(scale, repeats)
        for rec in w1["ingest"]:
            print(f"# W1 ingest: batch={rec['batch_size']} "
                  f"{rec['triples_per_s']:.0f} triples/s")
        print(f"# W1: rows={w1['rows']} inserted={w1['inserted']} "
              f"deleted={w1['deleted']} "
              f"warm_before={w1['warm_ms_before_writes']:.2f}ms "
              f"warm_after_writes={w1['warm_ms_after_writes']:.2f}ms "
              f"warm_after_compact={w1['warm_ms_after_compact']:.2f}ms "
              f"compact={w1['compact_ms']:.2f}ms")
        with open("BENCH_7.json", "w") as f:
            json.dump({"scale": scale, "repeats": repeats,
                       "updates": w1}, f, indent=2)
        print("# wrote BENCH_7.json")
    # D1 + D2: sharded vs single-device execution on 1 vs 4 devices
    # (forced host devices on the CPU); prints the shard-count scaling and asserts the per-shard bucket win
    # (D1) and the zero-shuffle subject-star + 4-device wall-time win (D2).
    sharded_records = bench_sharded(scale, repeats)
    for r in sharded_records:
        print(f"# {r['query']}: rows={r['rows']} "
              f"single_ms={r['single_ms']:.2f} "
              f"sharded_1dev_ms={r['sharded_1dev_ms']:.2f} "
              f"sharded_4dev_ms={r['sharded_4dev_ms']:.2f} "
              f"per_shard_max_bucket={r['per_shard_max_bucket']} "
              f"single_max_bucket={r['single_max_bucket']} "
              f"shuffles={r['shuffles_emitted']}e/"
              f"{r['shuffles_elided']}x/{r['broadcast_joins']}b")
    with open("BENCH_5.json", "w") as f:
        json.dump({"scale": scale, "repeats": repeats,
                   "device_counts": list(D1_DEVICE_COUNTS),
                   "sharded": sharded_records}, f, indent=2)
    print("# wrote BENCH_5.json")
    # D2 artifact: the shuffle-elision scaling story on its own — which
    # queries beat the 1-device mesh at 4 devices, and the per-query
    # emitted/elided/broadcast strategy counts
    wins = [r["query"] for r in sharded_records
            if r["sharded_4dev_ms"] < r["sharded_1dev_ms"]]
    with open("BENCH_8.json", "w") as f:
        json.dump({"scale": scale, "repeats": repeats,
                   "device_counts": list(D1_DEVICE_COUNTS),
                   "wall_time_wins_4dev": wins,
                   "star_queries_zero_emitted": [
                       r["query"] for r in sharded_records
                       if r["shuffles_emitted"] == 0
                   ],
                   "records": sharded_records}, f, indent=2)
    print(f"# wrote BENCH_8.json ({len(wins)} 4-device wall-time wins)")


if __name__ == "__main__":
    main()
