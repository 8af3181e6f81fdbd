"""Benchmark entry point: one benchmark per paper artifact.

  bench_join     — Table 2 / Figure 2: join time per LUBM query,
                   MapSQ vs gStore/gStoreD stand-ins (+ speedups)
  bench_query    — repeated (warm-cache) LUBM queries: eager per-join
                   loop vs the compiled one-dispatch pipeline
  bench_scaling  — Figure 2(b)-style: MapSQ vs hash join as relation
                   size grows (the 'large dataset scale' claim)
  bench_kernels  — Pallas kernels vs their jnp references (micro)
  roofline       — §Roofline table from the dry-run artifacts (if present)
"""
from __future__ import annotations

import time

import numpy as np

import jax
import jax.numpy as jnp


def _time(fn, repeat=3) -> float:
    best = float("inf")
    for _ in range(repeat):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def bench_scaling() -> None:
    """MapSQ MR join vs CPU hash join over growing relations (zipf keys)."""
    from repro.core.relation import Relation
    from repro.core import mr_join as mj
    from repro.sparql.baseline import hash_join

    print("\n# Figure 2(b)-style scaling: rows,hash_ms,mapsq_ms,speedup")
    jit_join = jax.jit(mj.mr_join, static_argnames=("capacity",))
    rng = np.random.default_rng(0)
    for n in (1 << 12, 1 << 14, 1 << 16, 1 << 18):
        # ~uniform keys: E[matches per row] ~ 2, so output stays O(n)
        keys_l = rng.integers(0, n // 2, n).astype(np.int32)
        keys_r = rng.integers(0, n // 2, n).astype(np.int32)
        left = Relation.from_numpy(
            ("?k", "?a"), np.stack([keys_l, np.arange(n)], 1))
        right = Relation.from_numpy(
            ("?k", "?b"), np.stack([keys_r, np.arange(n)], 1))
        total = int(mj.mr_join_count(left, right))
        cap = 1 << max(1, (total - 1).bit_length())
        run = lambda: jit_join(left, right, capacity=cap)[0].cols\
            .block_until_ready()
        run()
        t_dev = _time(run)
        la, ra = np.asarray(left.cols), np.asarray(right.cols)
        t_cpu = _time(lambda: hash_join(("?k", "?a"), la, ("?k", "?b"), ra))
        print(f"{n},{t_cpu * 1e3:.2f},{t_dev * 1e3:.2f},"
              f"{t_cpu / t_dev:.2f}  (result rows: {total})")


def bench_kernels() -> None:
    """Pallas kernel micro-shapes vs pure-jnp references (interpret mode on
    CPU: correctness + call overhead, not TPU latency)."""
    from repro.kernels.bitonic_sort import ops as sort_ops
    from repro.kernels.pair_expand import ops as pe_ops
    from repro.kernels.segment_reduce import ops as sr_ops

    print("\n# kernels: name,n,us_per_call (interpret-mode on CPU)")
    k = jax.random.randint(jax.random.PRNGKey(0), (4096,), 0, 1 << 20)
    v = jnp.arange(4096, dtype=jnp.int32)
    run = lambda: sort_ops.sort_pairs(k, v)[0].block_until_ready()
    run()
    print(f"bitonic_sort,4096,{_time(run) * 1e6:.0f}")
    counts = jax.random.randint(jax.random.PRNGKey(1), (512,), 0, 8)
    prefix = jnp.cumsum(counts, dtype=jnp.int32)
    run = lambda: pe_ops.pair_expand(prefix, counts, 4096)[0]\
        .block_until_ready()
    run()
    print(f"pair_expand,512x8,{_time(run) * 1e6:.0f}")
    data = jax.random.normal(jax.random.PRNGKey(2), (2048, 64))
    ids = jnp.sort(jax.random.randint(jax.random.PRNGKey(3), (2048,), 0, 128))
    run = lambda: sr_ops.sorted_segment_sum(data, ids, 128)\
        .block_until_ready()
    run()
    print(f"segment_reduce,2048x64,{_time(run) * 1e6:.0f}")


def main() -> None:
    from benchmarks import bench_join, bench_query
    from repro.launch.compile_cache import enable_compile_cache

    # before anything initialises jax: the D-series meshes need 4 devices
    bench_query.force_host_devices(max(bench_query.D1_DEVICE_COUNTS))
    enable_compile_cache()

    bench_join.main()
    bench_query.main()
    bench_scaling()
    bench_kernels()
    try:
        from benchmarks import roofline

        if roofline.load():
            print("\n(roofline dry-run artifacts present: "
                  "run `python -m benchmarks.roofline` for the full table)")
    except Exception:
        pass


if __name__ == "__main__":
    main()
