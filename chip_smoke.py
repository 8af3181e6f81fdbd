"""Bring-up smoke run: the SPARQL serving path on one TPU chip.

    python3 chip_smoke.py             # one chip: server, updates, kernels
    python3 chip_smoke.py --chips 4   # four chips: the sharded engine only

One chip: a `SPARQLServer(QueryEngine(store))` over a LUBM store of about
1.1M triples, built from seed 0, answers the five LUBM queries, S1 and the
F1/O1/U1 operator shapes in a cold round, a warm round and a concurrent
round (so same-shape requests stack into one dispatch); applies an
INSERT DATA / DELETE DATA request, compacts the store and asks again; then
runs S1 (matrix join: `match_layout` + `sort_ranks`) and Q9 (MR join:
`pair_expand`) on `QueryEngine(use_kernel=True)`. Every answer is checked
against the NumPy oracle (`sparql.baseline.reference_rows`); warm queries
must take 0 compiles and 1 dispatch; the kernel programs must contain a
Mosaic kernel (`tpu_custom_call`).

Four chips: a `SPARQLServer(ShardedQueryEngine)` over a 4-device mesh
answers the same queries on a LUBM scale-2 store (11.5k triples); each
answer must equal the single-device engine's and the oracle's, and
subject stars must emit 0 shuffles.

The wall times printed are those of a smoke run, not a benchmark. Any
failed check raises, so the exit code is non-zero; the script refuses to
run on anything but a TPU. The last line of stdout is the JSON result.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
from concurrent.futures import Future, ThreadPoolExecutor

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from benchmarks.bench_query import EXTRA_QUERIES  # noqa: E402
from repro.core import compat  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.serve.sparql_server import SPARQLServer  # noqa: E402
from repro.sparql import lubm  # noqa: E402
from repro.sparql.baseline import reference_rows  # noqa: E402
from repro.sparql.engine import QueryEngine, ShardedQueryEngine  # noqa: E402
from repro.sparql.parser import parse  # noqa: E402
from repro.sparql.sharded_store import shard_store  # noqa: E402

SCALE = 200  # LUBM universities: ~1.1M triples
# the four-chip check runs a small store: the cold path is compile-bound
# (one XLA TPU compile of a sort over >= 64k rows takes 16-20 s of host
# time), and on four chips every compile second is paid four times
SHARDED_SCALE = 2
SEED = 0
PLATFORM = "tpu"
KERNEL_MARK = "tpu_custom_call"  # a Mosaic kernel in compiled HLO
CONCURRENT_COPIES = 4  # requests per query in the concurrent round
REQUEST_TIMEOUT_MS = 600_000  # a cold stacked width compiles in-request

QUERIES = {
    **lubm.QUERIES,
    **lubm.S_QUERIES,
    **{k: EXTRA_QUERIES[k] for k in ("F1", "O1", "U1")},
}
# subject stars: every join key is the subject, so the subject-hash
# sharded scans are already co-located and no shuffle is emitted
STAR_QUERIES = {
    "Q1": lubm.QUERIES["Q1"],
    "Q4": lubm.QUERIES["Q4"],
    "STAR": lubm.PREFIX + """SELECT ?s ?a WHERE {
        ?s a ub:GraduateStudent . ?s ub:advisor ?a . }""",
}
E = "http://example.org/"
UPDATE = lubm.PREFIX + f"""
INSERT DATA {{
    <{E}StudentNew0> a ub:GraduateStudent .
    <{E}StudentNew0> ub:advisor <{E}Prof0_0_0> .
    <{E}StudentNew0> ub:takesCourse <{E}Course0_0_0> .
    <{E}StudentNew0> ub:memberOf <{E}Dept0_0> .
}} ;
DELETE DATA {{
    <{E}Dept0_0> ub:subOrganizationOf <{E}University0> .
    <{E}Prof0_0_0> ub:name "prof_0_0_0" .
}}"""


class CompileMeter:
    """Backend compile seconds and persistent-cache hits, from jax's own
    monitoring events (a cache hit's retrieval counts as its compile)."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.n_compiles = 0
        self.cache_hits = 0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += duration
                self.n_compiles += 1

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


def rows_key(rows) -> list:
    return sorted(tuple(sorted(r.items())) for r in rows)


def check(label: str, got, want) -> None:
    if rows_key(got) != want:
        raise AssertionError(
            f"{label}: {len(got)} rows differ from the oracle's {len(want)}"
        )


def oracle(store, texts: dict) -> dict:
    t0 = time.perf_counter()
    out = {n: rows_key(reference_rows(store, parse(t)))
           for n, t in texts.items()}
    print(f"oracle: {len(out)} queries in "
          f"{time.perf_counter() - t0:.1f} s (host, overlapping compiles)")
    return out


def oracle_async(pool: ThreadPoolExecutor, store, texts: dict) -> Future:
    """The oracle's answers on the store as it stands, computed on a pool
    thread while the device path compiles (XLA compiles release the GIL).
    The store must not be written before the future resolves."""
    return pool.submit(oracle, store, texts)


def build_store(scale: int):
    t0 = time.perf_counter()
    store = lubm.generate(scale=scale, seed=SEED, skew_shapes=True)
    print(f"store: {len(store)} triples, LUBM scale {scale} seed {SEED}, "
          f"built in {time.perf_counter() - t0:.1f} s")
    return store


def timed_query(srv: SPARQLServer, text: str):
    t0 = time.perf_counter()
    res = srv.query(text, timeout_ms=REQUEST_TIMEOUT_MS)
    return res, (time.perf_counter() - t0) * 1e3


def concurrent_round(srv: SPARQLServer, texts: dict, want: dict) -> None:
    results: dict[tuple, object] = {}

    def ask(name, i):
        try:
            results[(name, i)] = srv.query(texts[name],
                                           timeout_ms=REQUEST_TIMEOUT_MS)
        except Exception as e:  # re-raised on the main thread below
            results[(name, i)] = e

    # copies of one query arrive back to back, so a micro-batch holds
    # several requests of one plan shape: those stack into one dispatch
    threads = [threading.Thread(target=ask, args=(n, i))
               for n in texts for i in range(CONCURRENT_COPIES)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=REQUEST_TIMEOUT_MS / 1000)
        if t.is_alive():
            raise TimeoutError("a concurrent request never returned")
    for (name, i), res in sorted(results.items()):
        if isinstance(res, Exception):
            raise res
        check(f"{name} concurrent #{i}", res.rows, want[name])


def serve_phase(store, pool: ThreadPoolExecutor) -> Future:
    """Returns the oracle future for the store after its update, for the
    kernel phase, which runs on that store."""
    engine = QueryEngine(store)
    srv = SPARQLServer(engine)
    try:
        pending = oracle_async(pool, store, QUERIES)
        cold, cold_ms, warm_ms = {}, {}, {}
        for name, text in QUERIES.items():  # cold: calibrate + compile
            cold[name], cold_ms[name] = timed_query(srv, text)
        want = pending.result()
        rows = {}
        for name, res in cold.items():
            check(f"{name} cold", res.rows, want[name])
            rows[name] = len(res.rows)
        compiles0 = engine.plan_cache.compiles
        for name, text in QUERIES.items():  # warm, one request at a time
            res, warm_ms[name] = timed_query(srv, text)
            check(f"{name} warm", res.rows, want[name])
            st = engine.prepare(text).run().stats
            if st.n_compiles != 0 or st.n_dispatches != 1:
                raise AssertionError(
                    f"{name} warm: {st.n_compiles} compiles, "
                    f"{st.n_dispatches} dispatches (want 0 and 1)")
        warm_compiles = engine.plan_cache.compiles - compiles0
        if warm_compiles:
            raise AssertionError(f"warm round compiled {warm_compiles}x")
        print("per-query (smoke run wall times, not a benchmark):")
        for name in QUERIES:
            print(f"  {name}: rows={rows[name]} cold_ms={cold_ms[name]:.1f} "
                  f"warm_ms={warm_ms[name]:.2f}")
        print("warm round: 0 compiles, 1 dispatch per query")

        stacked0 = engine.stacked_dispatches
        concurrent_round(srv, QUERIES, want)
        stacked = engine.stacked_dispatches - stacked0
        if stacked == 0:
            raise AssertionError("the concurrent round never stacked")
        print(f"concurrent round: {len(QUERIES) * CONCURRENT_COPIES} "
              f"requests, {stacked} stacked dispatches, all equal to the "
              "oracle")

        res = srv.update(UPDATE)
        if (res.inserted, res.deleted) != (4, 2):
            raise AssertionError(f"update applied {res}")
        t0 = time.perf_counter()
        store.compact()
        print(f"update: +{res.inserted} -{res.deleted} rows, compacted in "
              f"{time.perf_counter() - t0:.1f} s")
        pending = oracle_async(pool, store, QUERIES)
        after = {name: timed_query(srv, text)[0]
                 for name, text in QUERIES.items()}
        want = pending.result()
        for name, res in after.items():
            check(f"{name} after update", res.rows, want[name])
        print("after update + compact: every answer equals the oracle")

        fallbacks, rejects = engine.stacked_fallbacks, engine.pad_rejects
        print(f"stacked-dispatch fallbacks: {fallbacks}, "
              f"padding rejects: {rejects}")
        if fallbacks or rejects:
            raise AssertionError("a stacked dispatch fell back or padding "
                                 "was rejected")
    finally:
        srv.close()
    return pending


def kernel_phase(store, pending: Future) -> None:
    """S1 and Q9 with the Pallas kernels; `pending` is the oracle for the
    store as it stands, still being computed."""
    engine = QueryEngine(store, use_kernel=True)
    runs = {}
    for name in ("S1", "Q9"):
        pq = engine.prepare(QUERIES[name])
        runs[name] = (pq.run(), pq.run())
    want = pending.result()
    for name, (cold, warm) in runs.items():
        check(f"{name} kernel cold", cold.rows, want[name])
        check(f"{name} kernel warm", warm.rows, want[name])
    backends = {}
    for entry in engine.plan_cache.entries():
        text = entry.compiled.executable.as_text()
        for b in entry.shape.join_backends:
            backends[b] = backends.get(b, 0) + text.count(KERNEL_MARK)
    print(f"kernel phase: {KERNEL_MARK} count by join backend {backends}")
    if not backends.get("matrix") or not backends.get("mr"):
        raise AssertionError(
            "S1 (matrix) and Q9 (mr) must both run a Mosaic kernel")


def sharded_phase(store, n_chips: int, pool: ThreadPoolExecutor) -> None:
    import jax

    mesh = compat.make_mesh((n_chips,), ("shards",),
                            devices=jax.devices()[:n_chips])
    t0 = time.perf_counter()
    sharded = ShardedQueryEngine(shard_store(store, n_chips), mesh=mesh)
    print(f"sharded store over {n_chips} chips: per-shard triples "
          f"{sharded.store.shard_sizes()} "
          f"({time.perf_counter() - t0:.1f} s)")
    single = QueryEngine(store)
    texts = {**QUERIES, **STAR_QUERIES}
    pending = oracle_async(pool, store, texts)
    srv = SPARQLServer(sharded)
    try:
        runs = {}
        for name, text in texts.items():
            cold, cold_ms = timed_query(srv, text)
            warm, warm_ms = timed_query(srv, text)
            runs[name] = (cold, warm, single.query(text), cold_ms, warm_ms)
        want = pending.result()
        print("per-query (smoke run wall times, not a benchmark):")
        for name, (cold, warm, one, cold_ms, warm_ms) in runs.items():
            check(f"{name} sharded", cold.rows, want[name])
            check(f"{name} sharded warm", warm.rows, want[name])
            check(f"{name} single-device", one, want[name])
            print(f"  {name}: rows={len(warm.rows)} cold_ms={cold_ms:.1f} "
                  f"warm_ms={warm_ms:.2f}")
    finally:
        srv.close()
    for name, text in STAR_QUERIES.items():
        st = sharded.prepare(text).run().stats
        if st.n_shuffles_emitted != 0 or st.n_dispatches != 1:
            raise AssertionError(f"{name}: {st}")
    print("sharded == single-device == oracle on every query; subject "
          f"stars {sorted(STAR_QUERIES)} emitted 0 shuffles")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded phase over four chips")
    args = ap.parse_args(argv)
    cache_dir = enable_compile_cache()
    import jax

    devices = jax.devices()
    dev = devices[0]
    print(f"jax {jax.__version__}: platform={dev.platform} "
          f"device_kind={dev.device_kind} devices={len(devices)}")
    if dev.platform != PLATFORM:
        raise SystemExit(f"no {PLATFORM} device (jax found "
                         f"{dev.platform}); this smoke run never falls back")
    if len(devices) < args.chips:
        raise SystemExit(f"--chips {args.chips} but jax sees "
                         f"{len(devices)} devices")
    print(f"compile cache: {cache_dir}")
    meter = CompileMeter()
    t0 = time.perf_counter()
    with ThreadPoolExecutor(max_workers=1) as pool:
        if args.chips == 1:
            store = build_store(SCALE)
            kernel_phase(store, serve_phase(store, pool))
        else:
            sharded_phase(build_store(SHARDED_SCALE), args.chips, pool)
    stats = dev.memory_stats() or {}
    print(f"compile: {meter.n_compiles} backend compiles, "
          f"{meter.seconds:.1f} s, {meter.cache_hits} persistent-cache hits")
    print(f"peak_bytes_in_use (device 0): "
          f"{stats.get('peak_bytes_in_use', 'not reported')}")
    print(f"total wall {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
