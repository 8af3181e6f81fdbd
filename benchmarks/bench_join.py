"""Table 2 / Figure 2 reproduction: join time of the 5 LUBM queries —
MapSQ's MapReduce join (device, jitted) vs the CPU-engine join class.

Baseline mapping (see sparql/baseline.py):
  gStore   → hash_join            (build/probe, the centralized CPU engine)
  gStoreD  → partitioned_hash_join (partition pass + local joins)
  (plain)  → nested_loop_join     (the paper's 'plain join algorithm';
                                    only run when inputs are small)

The numbers reproduce the COMPARISON SHAPE of Table 2 (same partial
matches in, same results out, join time measured); absolute ratios on this
CPU-only container are indicative, not TPU measurements — see EXPERIMENTS.md.
"""
from __future__ import annotations

import time

import numpy as np

import jax

from repro.core import mr_join as mj
from repro.core.planner import plan_bgp
from repro.launch.compile_cache import enable_compile_cache
from repro.sparql import lubm
from repro.sparql.baseline import (hash_join, nested_loop_join,
                                   partitioned_hash_join)
from repro.sparql.engine import QueryEngine
from repro.sparql.parser import parse
from repro.sparql.store import _next_pow2

NESTED_LOOP_MAX = 3000  # rows; python nested loop beyond this is pointless


def _time(fn, repeat=3, number=1) -> float:
    best = float("inf")
    for _ in range(repeat):
        t0 = time.perf_counter()
        for _ in range(number):
            fn()
        best = min(best, (time.perf_counter() - t0) / number)
    return best


def _mapsq_join_chain(partials):
    """The jitted Algorithm-1 chain (count pass + expand pass per step)."""
    jit_count = jax.jit(mj.mr_join_count)
    jit_join = jax.jit(mj.mr_join, static_argnames=("capacity",))

    def run():
        acc = partials[0]
        for nxt in partials[1:]:
            total = int(jit_count(acc, nxt))
            cap = max(1, _next_pow2(total))
            acc, _, _ = jit_join(acc, nxt, capacity=cap)
        return acc.cols.block_until_ready()

    return run


def bench(scale: int = 3, seed: int = 0) -> list[dict]:
    store = lubm.generate(scale=scale, seed=seed)
    eng = QueryEngine(store)
    rows_out = []
    for name, text in lubm.QUERIES.items():
        q = parse(text)
        steps = plan_bgp(q.patterns, store.estimate_cardinality)
        partials = [store.match_pattern(q.patterns[s.pattern_index])
                    for s in steps]
        np_parts = [(p.schema, p.to_numpy()) for p in partials]
        sizes = [len(r) for _, r in np_parts]

        run_mapsq = _mapsq_join_chain(partials)
        run_mapsq()  # warm the jit cache: measure join time, not compile
        t_mapsq = _time(run_mapsq)

        def chain(join):
            def run():
                sch, rows = np_parts[0]
                for sch2, rows2 in np_parts[1:]:
                    sch, rows = join(sch, rows, sch2, rows2)
                return rows

            return run

        t_hash = _time(chain(hash_join))
        t_part = _time(chain(partitioned_hash_join))
        t_nested = (
            _time(chain(nested_loop_join), repeat=1)
            if max(sizes) <= NESTED_LOOP_MAX else float("nan")
        )
        n_result = len(chain(hash_join)())
        rows_out.append({
            "query": name,
            "inputs": "x".join(map(str, sizes)),
            "n_result": n_result,
            "gStore_ms": t_hash * 1e3,
            "gStoreD_ms": t_part * 1e3,
            "MapSQ_ms": t_mapsq * 1e3,
            "nested_ms": t_nested * 1e3,
            "SpeedUp_g": t_hash / t_mapsq,
            "SpeedUp_D": t_part / t_mapsq,
        })
    return rows_out


def main() -> None:
    enable_compile_cache()
    print("# Table 2 reproduction: join time (ms), LUBM scale=3")
    print("query,inputs,n_result,gStore_ms,gStoreD_ms,MapSQ_ms,nested_ms,"
          "SpeedUp_g,SpeedUp_D")
    for r in bench():
        print(f"{r['query']},{r['inputs']},{r['n_result']},"
              f"{r['gStore_ms']:.2f},{r['gStoreD_ms']:.2f},"
              f"{r['MapSQ_ms']:.2f},{r['nested_ms']:.2f},"
              f"{r['SpeedUp_g']:.2f},{r['SpeedUp_D']:.2f}")


if __name__ == "__main__":
    main()
