"""Algorithm 1 (MapReduce join) vs a python oracle, incl. hypothesis sweeps."""
import itertools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # property tests skip without the dev extra
    from _hypothesis_compat import given, settings, st

from repro.core import mr_join as mj
from repro.core.relation import Relation, shared_vars
from repro.core.segments import dense_rank_two_sided


def oracle_join(l_schema, l_rows, r_schema, r_rows):
    """Nested-loop natural join with python sets (ground truth)."""
    shared = [v for v in l_schema if v in r_schema]
    r_extra = [v for v in r_schema if v not in l_schema]
    out = []
    for lr in l_rows:
        for rr in r_rows:
            if all(lr[l_schema.index(v)] == rr[r_schema.index(v)] for v in shared):
                out.append(tuple(lr) + tuple(rr[r_schema.index(v)] for v in r_extra))
    return out


def make_rel(schema, rows, capacity=None):
    return Relation.from_numpy(schema, np.array(rows, np.int32).reshape(-1, len(schema)),
                               capacity=capacity)


def run_join(l_schema, l_rows, r_schema, r_rows, capacity=None, **kw):
    left = make_rel(l_schema, l_rows)
    right = make_rel(r_schema, r_rows)
    expected = oracle_join(l_schema, l_rows, r_schema, r_rows)
    cap = capacity or max(1, 2 * len(expected) + 4)
    out, total, overflowed = mj.mr_join(left, right, cap, **kw)
    assert int(total) == len(expected)
    assert not bool(overflowed)
    got = sorted(map(tuple, out.to_numpy().tolist()))
    assert got == sorted(expected)
    return out


def test_paper_table1_example():
    """The exact example of Table 1: persons/jobs joined on ?job."""
    # dictionary: Professor=0 Doctor=1 Nurse=2 Anny=3 Jim=4 Susan=5 Hospital=6
    tp1 = [(0, 3), (1, 4), (2, 5)]  # (?job, ?person)
    tp2 = [(1, 6), (2, 6)]  # (?job, "Hospital"-bound object col)
    out = run_join(("?job", "?person"), tp1, ("?job", "?o"), tp2)
    assert out.to_set() == {(1, 4, 6), (2, 5, 6)}  # Doctor/Jim, Nurse/Susan


def test_duplicate_keys_cartesian_within_group():
    l = [(7, i) for i in range(4)] + [(8, 9)]
    r = [(7, 100 + j) for j in range(3)]
    run_join(("?k", "?a"), l, ("?k", "?b"), r)


def test_no_matches():
    out = run_join(("?k", "?a"), [(1, 2)], ("?k", "?b"), [(3, 4)])
    assert out.to_set() == set()


def test_multi_variable_key():
    l = [(1, 2, 10), (1, 3, 11), (2, 2, 12)]
    r = [(1, 2, 20), (2, 2, 21), (2, 2, 22)]
    run_join(("?x", "?y", "?a"), l, ("?x", "?y", "?b"), r)


def test_overflow_flag():
    left = make_rel(("?k", "?a"), [(1, i) for i in range(8)])
    right = make_rel(("?k", "?b"), [(1, i) for i in range(8)])
    out, total, overflowed = mj.mr_join(left, right, capacity=16)
    assert int(total) == 64 and bool(overflowed)
    # truncated but the reported rows are real join rows
    rows = out.to_numpy()
    assert len(rows) == 16 and set(rows[:, 0].tolist()) == {1}


def test_padding_rows_never_join():
    left = make_rel(("?k", "?a"), [(0, 1)], capacity=8)  # 7 invalid zero rows
    right = make_rel(("?k", "?b"), [(0, 2)], capacity=8)
    out, total, _ = mj.mr_join(left, right, 8)
    assert int(total) == 1
    assert out.to_set() == {(0, 1, 2)}


def test_jit_count_and_expand_agree():
    left = make_rel(("?k", "?a"), [(i % 3, i) for i in range(32)])
    right = make_rel(("?k", "?b"), [(i % 5, i) for i in range(32)])
    count = jax.jit(mj.mr_join_count)(left, right)
    out, total, _ = jax.jit(mj.mr_join, static_argnums=2)(left, right, 512)
    assert int(count) == int(total)


def test_cross_join():
    left = make_rel(("?a",), [(1,), (2,)])
    right = make_rel(("?b",), [(5,), (6,), (7,)])
    out, total, ov = mj.cross_join(left, right, 8)
    assert int(total) == 6 and not bool(ov)
    assert out.to_set() == set(
        (a, b) for a in (1, 2) for b in (5, 6, 7)
    )


def test_distinct_and_compact():
    rel = make_rel(("?a", "?b"), [(1, 2), (1, 2), (3, 4), (0, 0)], capacity=8)
    d = mj.distinct(rel)
    assert d.to_set() == {(1, 2), (3, 4), (0, 0)}
    assert int(d.count()) == 3
    c = mj.compact(d)
    assert bool(np.all(np.asarray(c.valid)[: int(d.count())]))


def test_semijoin_mask():
    left = make_rel(("?k", "?a"), [(1, 10), (2, 11), (3, 12)])
    right = make_rel(("?k", "?b"), [(1, 0), (3, 0)])
    mask = mj.semijoin_mask(left, right)
    np.testing.assert_array_equal(np.asarray(mask), [True, False, True])


@st.composite
def relation_pair(draw):
    n_keys = draw(st.integers(1, 5))
    l_rows = draw(st.lists(st.tuples(st.integers(0, n_keys), st.integers(0, 6)),
                           min_size=1, max_size=24))
    r_rows = draw(st.lists(st.tuples(st.integers(0, n_keys), st.integers(0, 6)),
                           min_size=1, max_size=24))
    return l_rows, r_rows


@settings(max_examples=60, deadline=None)
@given(relation_pair())
def test_hypothesis_matches_oracle(pair):
    l_rows, r_rows = pair
    run_join(("?k", "?a"), l_rows, ("?k", "?b"), r_rows)


@settings(max_examples=40, deadline=None)
@given(st.lists(st.tuples(st.integers(0, 3), st.integers(0, 3), st.integers(0, 5)),
                min_size=1, max_size=16),
       st.lists(st.tuples(st.integers(0, 3), st.integers(0, 3), st.integers(0, 5)),
                min_size=1, max_size=16))
def test_hypothesis_multivar(l_rows, r_rows):
    run_join(("?x", "?y", "?a"), l_rows, ("?x", "?y", "?b"), r_rows)


# -- the co-sort count against the binary-search count it replaced ---------


def search_plan(lk, rk):
    """The two-binary-search count (oracle): (n, k) sentinel-mapped keys."""
    if lk.shape[1] > 1:
        lk, rk = (r[:, None] for r in dense_rank_two_sided(lk, rk))
    order_l, order_r = jnp.argsort(lk[:, 0]), jnp.argsort(rk[:, 0])
    l_s, r_s = lk[order_l, 0], rk[order_r, 0]
    lo = jnp.searchsorted(r_s, l_s, side="left").astype(jnp.int32)
    counts = jnp.searchsorted(r_s, l_s, side="right").astype(jnp.int32) - lo
    prefix = jnp.cumsum(counts, dtype=jnp.int32)
    total = prefix[-1] if counts.shape[0] else jnp.int32(0)
    return mj.JoinPlanArrays(order_l, order_r, lo, counts, prefix, total)


def plan_case(n_l, n_r, k, n_keys, fill, seed):
    """Two relations over k shared variables: `fill` of each capacity valid,
    keys drawn from n_keys values (UNBOUND among them), padding rows
    holding key values too."""
    rng = np.random.default_rng(seed)
    ks = tuple(f"?k{c}" for c in range(k))

    def rel(schema, n):
        cols = rng.integers(-1, n_keys - 1, (n, len(schema)), dtype=np.int32)
        valid = rng.random(n) < fill
        return Relation(schema, jnp.asarray(cols), jnp.asarray(valid))

    return rel(ks + ("?a",), n_l), rel(("?b",) + ks, n_r)


PLAN_CASES = {
    "single_key": (64, 128, 1, 20, 0.8),
    "two_keys": (64, 128, 2, 4, 0.8),
    "three_keys": (32, 64, 3, 3, 0.9),
    "heavy_duplicates": (64, 64, 1, 2, 1.0),
    "one_key_everywhere": (16, 32, 2, 1, 0.7),
    "all_invalid_right": (32, 64, 1, 8, 0.0),
    "tiny_left_huge_right": (2, 4096, 1, 50, 0.9),
    "huge_left_tiny_right": (4096, 2, 1, 50, 0.9),
    "one_row_each": (1, 1, 1, 1, 1.0),
}


def assert_plans_equal(got, want):
    for field, a, b in zip(want._fields, want, got):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=field)


@pytest.mark.parametrize("case", sorted(PLAN_CASES))
def test_cosort_plan_equals_binary_search(case):
    left, right = plan_case(*PLAN_CASES[case], seed=len(case))
    got = jax.jit(lambda l, r: mj.mr_join_plan(l, r)[0])(left, right)
    lk, rk = mj._key_columns(left, right, shared_vars(left, right))
    assert_plans_equal(got, search_plan(lk, rk))


def test_cosort_plan_equals_binary_search_all_invalid_left():
    left, right = plan_case(64, 32, 2, 6, 0.8, seed=1)
    left = Relation(left.schema, left.cols, jnp.zeros_like(left.valid))
    got, key_vars = mj.mr_join_plan(left, right)
    assert int(got.total) == 0
    assert_plans_equal(got, search_plan(*mj._key_columns(left, right,
                                                         key_vars)))


@pytest.mark.parametrize("k", [1, 2])
def test_cosort_plan_equals_binary_search_vmapped(k):
    """The stacked program's form: one plan per lane under vmap."""
    lanes = [plan_case(256, 512, k, 40, 0.8, seed=s) for s in (3, 4)]
    ls, rs = lanes[0][0].schema, lanes[0][1].schema

    def plan(lc, lv, rc, rv):
        return mj.mr_join_plan(Relation(ls, lc, lv), Relation(rs, rc, rv))[0]

    stack = lambda xs: jnp.stack(xs)
    got = jax.jit(jax.vmap(plan))(
        stack([l.cols for l, _ in lanes]), stack([l.valid for l, _ in lanes]),
        stack([r.cols for _, r in lanes]), stack([r.valid for _, r in lanes]))
    for i, (left, right) in enumerate(lanes):
        lk, rk = mj._key_columns(left, right, shared_vars(left, right))
        assert_plans_equal(jax.tree.map(lambda a: a[i], got),
                           search_plan(lk, rk))


def phase_whiles(hlo_text: str) -> list[str]:
    """The MR-join phase (map/sort/count/expand) of every `while` in a
    lowered module's HLO: the phase named by the outermost instruction on
    the chain of calls that reaches the loop."""
    caller, comp = {}, ""
    whiles = []
    for line in hlo_text.splitlines():
        if line and not line[0].isspace() and line.rstrip().endswith("{"):
            comp = line.split()[-2].lstrip("%")
            continue
        op_name = re.search(r'op_name="([^"]*)"', line)
        for callee in re.findall(
                r"(?:to_apply|body|condition|calls)=%?([\w.\-]+)", line):
            caller[callee] = (comp, op_name.group(1) if op_name else "")
        if " while(" in line:
            whiles.append(comp)
    phases = []
    for comp in whiles:
        outer = ""
        while comp in caller:
            comp, outer = caller[comp][0], caller[comp][1] or outer
        phases.append(outer.split("/")[1])
    return phases


def test_count_has_no_while_at_the_cells_join_shape():
    """65,536 left rows into 2^20 right rows, the heaviest join of the
    benchmark cell: lowered only, no `while` left in map, sort or count
    (expand keeps its search over the prefix sums)."""
    def rel(schema, n):
        return Relation(schema, jax.ShapeDtypeStruct((n, 2), jnp.int32),
                        jax.ShapeDtypeStruct((n,), jnp.bool_))

    lowered = jax.jit(mj.mr_join, static_argnames="capacity").lower(
        rel(("?a", "?k"), 1 << 16), rel(("?k", "?b"), 1 << 20),
        capacity=1 << 20)
    phases = phase_whiles(lowered.as_text(dialect="hlo", debug_info=True))
    assert phases == ["expand"]
