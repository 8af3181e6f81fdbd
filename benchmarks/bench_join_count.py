"""Microbenchmark of the MR join's match count: binary search vs co-sort.

Times the sort + count half of Algorithm 1 (`JoinPlanArrays` from the
mapped key columns), which every MR join, OPTIONAL and FILTER EXISTS pays
before `expand`, in five forms:

  search         argsort each side, then two `jnp.searchsorted` of the
                 sorted left keys in the sorted right keys; multi-variable
                 keys dense-ranked first (`dense_rank_two_sided`)
  cosort         what `mr_join` runs (`mr_join._sort_count_phase`): one
                 sort of both sides' keys, ties by row (left first); counts
                 from prefix counts and a reverse cummin over key-group
                 ends, both as blocked scans; back to sorted-left /
                 sorted-right order by a sort on each row's destination;
                 under vmap all lanes sort as one array
  cosort_sort    the same co-sort with stable `lax.sort` on the keys alone,
                 whole-array scans, a stable 1-bit partition sort, and
                 XLA's own batched sort under vmap
  cosort_scatter cosort_sort, partitioned by one permutation scatter
  merged_scatter no `order_r`: right rows are gathered from the merged
                 order (`lo` indexes it); left partitioned by a scatter

Every form is checked against `search` on the CPU (`--check`;
tests/test_core_join.py holds the same checks for `mr_join`). On the chip,
from the checkout root:

    PYTHONPATH=src python3 benchmarks/bench_join_count.py --lubm
    PYTHONPATH=src python3 benchmarks/bench_join_count.py \
        --case 65536,1048576,2,2,search+cosort ...

Each prints one JSON line per (shape, width, form): the median of the
jitted plan's wall time around `block_until_ready`, its compile time and
the `while` ops of its optimized HLO. `--lubm` builds the `lubm20`
benchmark store, runs L1, L2, L3 and L7 once, prints their `explain()`,
and times every MR join shape their plans hold, at width 1, in every form.
All programs compile in parallel before any is timed.
"""
from __future__ import annotations

import argparse
import concurrent.futures
import json
import os
import statistics
import sys
import time

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import mr_join as mj
from repro.core.relation import INVALID_LEFT, INVALID_RIGHT
from repro.core.segments import dense_rank_two_sided

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _plan(order_l, order_r, lo, counts) -> mj.JoinPlanArrays:
    prefix = jnp.cumsum(counts, dtype=jnp.int32)
    total = prefix[-1] if counts.shape[0] else jnp.int32(0)
    return mj.JoinPlanArrays(order_l, order_r, lo, counts, prefix, total)


def plan_search(lk, rk) -> mj.JoinPlanArrays:
    """The binary-search count: (n_l, k), (n_r, k) sentinel-mapped keys."""
    if lk.shape[1] == 1:
        l_key, r_key = lk[:, 0], rk[:, 0]
    else:
        l_key, r_key = dense_rank_two_sided(lk, rk)
    order_l = jnp.argsort(l_key)
    order_r = jnp.argsort(r_key)
    lk_s, rk_s = l_key[order_l], r_key[order_r]
    lo = jnp.searchsorted(rk_s, lk_s, side="left").astype(jnp.int32)
    hi = jnp.searchsorted(rk_s, lk_s, side="right").astype(jnp.int32)
    return _plan(order_l.astype(jnp.int32), order_r.astype(jnp.int32), lo,
                 hi - lo)


def _cosort(lk, rk):
    """Stable co-sort on the keys alone; per merged position: row, is-right,
    right rows before it, the inclusive right count at its key group's end
    and the inclusive left count there."""
    n_l, n_r = lk.shape[0], rk.shape[0]
    keys = jnp.concatenate([lk, rk], axis=0)
    row = jnp.arange(n_l + n_r, dtype=jnp.int32)
    cols = [keys[:, c] for c in range(keys.shape[1])]
    *s_keys, s_row = lax.sort((*cols, row), num_keys=len(cols),
                              is_stable=True)
    is_r = (s_row >= n_l).astype(jnp.int32)
    r_incl = jnp.cumsum(is_r, dtype=jnp.int32)
    last = jnp.stack([c != jnp.roll(c, -1) for c in s_keys]).any(axis=0)
    hi = lax.cummin(jnp.where(last, r_incl, n_r), reverse=True)
    l_end = lax.cummin(jnp.where(last, row + 1 - r_incl, n_l), reverse=True)
    return s_row, is_r, r_incl - is_r, hi, l_end


def plan_cosort_sort(lk, rk) -> mj.JoinPlanArrays:
    n_l = lk.shape[0]
    s_row, is_r, lo, hi, _ = _cosort(lk, rk)
    _, p_row, p_lo, p_cnt = lax.sort((is_r, s_row, lo, hi - lo),
                                     num_keys=1, is_stable=True)
    return _plan(p_row[:n_l], p_row[n_l:] - n_l, p_lo[:n_l], p_cnt[:n_l])


def plan_cosort_scatter(lk, rk) -> mj.JoinPlanArrays:
    n_l = lk.shape[0]
    s_row, is_r, lo, hi, _ = _cosort(lk, rk)
    pos = jnp.arange(s_row.shape[0], dtype=jnp.int32)
    dest = jnp.where(is_r == 1, n_l + lo, pos - lo)  # a permutation
    payload = jnp.stack([s_row, lo, hi - lo], axis=1)
    out = jnp.zeros_like(payload).at[dest].set(
        payload, unique_indices=True)
    return _plan(out[:n_l, 0], out[n_l:, 0] - n_l, out[:n_l, 1],
                 out[:n_l, 2])


def plan_merged_scatter(lk, rk) -> mj.JoinPlanArrays:
    """`lo` is the merged position of a left row's first match: its key
    group's right rows follow the group's left rows contiguously."""
    n_l = lk.shape[0]
    s_row, is_r, lo, hi, l_end = _cosort(lk, rk)
    pos = jnp.arange(s_row.shape[0], dtype=jnp.int32)
    dest = jnp.where(is_r == 1, n_l + lo, pos - lo)  # right rows dropped
    payload = jnp.stack([s_row, lo + l_end, hi - lo], axis=1)
    out = jnp.zeros((n_l, 3), jnp.int32).at[dest].set(
        payload, mode="drop", unique_indices=True)
    return _plan(out[:, 0], s_row - n_l, out[:, 1], out[:, 2])


FORMS = {
    "search": plan_search,
    "cosort": mj._sort_count_phase,
    "cosort_sort": plan_cosort_sort,
    "cosort_scatter": plan_cosort_scatter,
    "merged_scatter": plan_merged_scatter,
}


def sample_keys(key, n_l: int, n_r: int, k: int, fill: float = 0.75):
    """Sentinel-mapped key columns: ids drawn so that keys repeat on both
    sides, and a quarter of each side padding (pow-2 buckets fill 50-100%)."""
    kl, kr, vl, vr = jax.random.split(key, 4)
    span = jnp.array([max(2, n_r // 4)] + [4] * (k - 1), jnp.int32)
    lk = jax.random.randint(kl, (n_l, k), 0, span, jnp.int32)
    rk = jax.random.randint(kr, (n_r, k), 0, span, jnp.int32)
    lvalid = jax.random.uniform(vl, (n_l, 1)) < fill
    rvalid = jax.random.uniform(vr, (n_r, 1)) < fill
    return (jnp.where(lvalid, lk, INVALID_LEFT),
            jnp.where(rvalid, rk, INVALID_RIGHT))


def pairs(plan: mj.JoinPlanArrays) -> list[tuple[int, int]]:
    """Every (left row, right row) pair a plan emits, in emission order."""
    import numpy as np

    order_l, order_r, lo, counts = (np.asarray(a) for a in plan[:4])
    return [(int(order_l[i]), int(order_r[lo[i] + off]))
            for i in range(order_l.shape[0])
            for off in range(int(counts[i]))]


def check(n_l: int = 200, n_r: int = 300, seed: int = 0) -> None:
    """Every form emits the search's pairs, solo and in two vmapped lanes;
    all but merged_scatter match its six fields bit for bit."""
    import numpy as np

    for k in (1, 2):
        keys = jax.random.split(jax.random.PRNGKey(seed + k), 2)
        lk, rk = jax.vmap(lambda s: sample_keys(s, n_l, n_r, k))(keys)
        for name, fn in FORMS.items():
            got = jax.jit(jax.vmap(fn))(lk, rk)
            for lane in range(2):
                want = plan_search(lk[lane], rk[lane])
                one = jax.tree.map(lambda a: a[lane], got)
                assert np.array_equal(want.counts, one.counts), (name, k)
                assert pairs(one) == pairs(want), (name, k)
                if name != "merged_scatter":
                    for f, a, b in zip(want._fields, want, one):
                        assert np.array_equal(a, b), (name, k, f)


def lubm_shapes(seed: int) -> list[tuple[int, int, int]]:
    """Build the lubm20 benchmark store, run L1, L2, L3 and L7 once, print
    their plans, and return the (n_left, n_right, key columns) of every MR
    join (inner or OPTIONAL) their compiled plans hold, largest first."""
    sys.path.insert(0, os.path.join(ROOT, "bench"))
    import uba
    from repro.core import executor as ex
    from repro.core import plan_ir
    from repro.sparql.dictionary import TermDict
    from repro.sparql.engine import QueryEngine
    from repro.sparql.store import TripleStore

    with open(os.path.join(ROOT, "bench", "configs", "lubm20.json")) as f:
        config = json.load(f)
    with open(os.path.join(ROOT, "bench", "queries", "lubm.json")) as f:
        qs = json.load(f)
    data = uba.generate(config, seed)
    d = TermDict()
    d.encode_many(data.terms)
    engine = QueryEngine(TripleStore(data.triples, d))
    for name in ("L1", "L2", "L3", "L7"):
        pq = engine.prepare(qs["prefix"] + qs["queries"][name])
        pq.run()
        print(f"# {name}\n" + pq.explain(), file=sys.stderr)
    shapes = {
        (node.left.capacity, node.right.capacity, len(node.key_vars))
        for entry in engine.plan_cache.entries()
        for node in ex.join_slot_nodes(entry.compiled.plan)
        if isinstance(node, (plan_ir.MRJoin, plan_ir.LeftJoin))
    }
    return sorted(shapes, key=lambda s: (-s[0] * s[1], s))


def compile_case(case: tuple, seed: int):
    """(compiled plan, its inputs, compile seconds) of one case."""
    n_l, n_r, k, width, form = case
    key = jax.random.PRNGKey(seed)
    fn = FORMS[form]
    if width == 1:
        lk, rk = sample_keys(key, n_l, n_r, k)
    else:
        lk, rk = jax.vmap(lambda s: sample_keys(s, n_l, n_r, k))(
            jax.random.split(key, width))
        fn = jax.vmap(fn)
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(lk, rk).compile()
    return compiled, (lk, rk), time.perf_counter() - t0


def time_case(compiled, args, reps: int) -> float:
    jax.block_until_ready(compiled(*args))
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(compiled(*args))
        ts.append(time.perf_counter() - t0)
    return 1e3 * statistics.median(ts)


def parse_case(text: str) -> list[tuple]:
    """'n_l,n_r,k,width,form+form' -> one case per form."""
    n_l, n_r, k, width, forms = text.split(",")
    return [(int(n_l), int(n_r), int(k), int(width), f)
            for f in forms.split("+")]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--check", action="store_true",
                    help="check every form against search, and stop")
    ap.add_argument("--lubm", action="store_true",
                    help="time the join shapes of the lubm20 cell's plans")
    ap.add_argument("--case", action="append", default=[],
                    help="n_l,n_r,k,width,form[+form...]")
    ap.add_argument("--seed", type=int, default=8400000101)
    ap.add_argument("--reps", type=int, default=10)
    args = ap.parse_args()
    if args.check:
        check()
        print("every form agrees with search")
        return
    dev = jax.devices()[0]
    print(json.dumps({"platform": dev.platform,
                      "device_kind": dev.device_kind}), flush=True)
    cases = [c for text in args.case for c in parse_case(text)]
    if args.lubm:
        shapes = lubm_shapes(args.seed)
        print(json.dumps({"shapes": shapes}), flush=True)
        cases += [(*s, 1, f) for s in shapes for f in FORMS]
    with concurrent.futures.ThreadPoolExecutor(os.cpu_count()) as pool:
        built = list(pool.map(lambda c: compile_case(c, args.seed), cases))
    for (n_l, n_r, k, width, form), (compiled, inputs, compile_s) in zip(
            cases, built):
        print(json.dumps({
            "n_l": n_l, "n_r": n_r, "k": k, "width": width, "form": form,
            "median_ms": time_case(compiled, inputs, args.reps),
            "compile_s": compile_s,
            "while_ops": compiled.as_text().count(" while(")}), flush=True)


if __name__ == "__main__":
    main()
