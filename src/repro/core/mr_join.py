"""Algorithm 1 of MapSQ: the MapReduce-based join, TPU-native.

Three phases, exactly as the paper structures them:

  Map             — split every tuple into (key, value); tag side. Invalid
                    (padding) rows are mapped to per-side sentinel keys so
                    they can never join (the LEFT/RIGHT flag's purpose —
                    "reduce unnecessary computation" — achieved structurally).
  Sort            — sort both sides by key together (the shuffle): one
                    co-sort of the key columns, ties broken by row so left
                    rows come first on equal keys. On TPU this is a bitonic
                    network (see kernels/bitonic_sort); here we use XLA's
                    sort, which lowers to the same thing.
  ReduceDuplicate — per key group, emit the cartesian product of LEFT values
                    with RIGHT values. Realised as: per-left-row match counts
                    read off the co-sorted order (right rows before the row,
                    and right rows up to its key group's end), prefix sum,
                    then a dense inverse-prefix-sum gather
                    (kernels/pair_expand) — one output element per lane,
                    perfectly load balanced.

Dynamic result size is handled Mars-style: a count pass returns the exact
total; the expand pass fills a static-capacity buffer with a validity mask.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.relation import (
    INVALID_LEFT,
    INVALID_RIGHT,
    UNBOUND,
    Relation,
    shared_vars,
)
from repro.core.segments import dense_rank_two_sided


# How `_sort_count_phase` counts matches, as EXPLAIN names it: one sort of
# both sides (it beat two binary searches per left row at every join shape
# of the LUBM(20) benchmark on a v5e chip, 64 to 65,536 left rows against
# 512 to 2^20 right rows, solo and vmapped; benchmarks/bench_join_count.py).
COUNT_METHOD = "co-sort"


class JoinPlanArrays(NamedTuple):
    """Sorted intermediates shared by the count and expand passes."""

    order_l: jax.Array  # (n_l,) permutation sorting left by key
    order_r: jax.Array  # (n_r,) permutation sorting right by key
    lo: jax.Array  # (n_l,) first matching right slot per sorted-left row
    counts: jax.Array  # (n_l,) number of right matches per sorted-left row
    prefix: jax.Array  # (n_l,) inclusive prefix sum of counts
    total: jax.Array  # () int32 exact number of join results


def _key_columns(left: Relation, right: Relation, key_vars: list[str]):
    """Map: the (n, k) key columns of each side, invalid rows set to
    per-side sentinels."""
    lk = jnp.stack([left.column(v) for v in key_vars], axis=1)
    rk = jnp.stack([right.column(v) for v in key_vars], axis=1)
    lk = jnp.where(left.valid[:, None], lk, INVALID_LEFT)
    rk = jnp.where(right.valid[:, None], rk, INVALID_RIGHT)
    return lk, rk


def _map_phase(left: Relation, right: Relation, key_vars: list[str]):
    """Map to one int32 key per row (the matrix backend's form)."""
    lk, rk = _key_columns(left, right, key_vars)
    if len(key_vars) == 1:
        return lk[:, 0], rk[:, 0]
    # Multi-variable join: dense-rank tuples jointly into a single int32
    # key. Sentinel rows keep never-equal ranks.
    return dense_rank_two_sided(lk, rk)


def _sort(operands: tuple, num_keys: int) -> tuple:
    """`lax.sort` by the first `num_keys` operands, the last of which is
    unique, so no stability is needed. Under vmap the lanes are sorted as
    one array, lane id first: XLA's TPU sort of a batch of rows runs
    several times slower than one sort of all of them."""

    @jax.custom_batching.custom_vmap
    def sort(*ops):
        return tuple(lax.sort(ops, num_keys=num_keys, is_stable=False))

    @sort.def_vmap
    def _(axis_size, in_batched, *ops):
        ops = [o if b else jnp.broadcast_to(o, (axis_size, *o.shape))
               for o, b in zip(ops, in_batched)]
        n = ops[0].shape[1]
        lane = jnp.repeat(jnp.arange(axis_size, dtype=jnp.int32), n)
        out = _sort((lane, *(o.reshape(-1) for o in ops)), num_keys + 1)
        out = tuple(o.reshape(axis_size, n) for o in out[1:])
        return out, (True,) * len(out)

    return sort(*operands)


_SCAN_BLOCK = 1024


def _scan(cum, op, x: jax.Array, fill, reverse: bool = False) -> jax.Array:
    """Inclusive scan `cum` (op, identity `fill`) of a 1-D array, as scans
    of 1024-row blocks and of the block totals. Same numbers as one scan;
    XLA's TPU compiler takes tens of seconds over a scan of a million rows
    and about a second over this."""
    n = x.shape[0]
    m = jnp.pad(x, (0, -n % _SCAN_BLOCK), constant_values=fill)
    m = cum(m.reshape(-1, _SCAN_BLOCK), axis=1, reverse=reverse)
    total = m[:, 0] if reverse else m[:, -1]
    carry = cum(total, reverse=reverse)  # through each block, inclusive
    fill_1 = jnp.full(1, fill, x.dtype)
    carry = (jnp.concatenate([carry[1:], fill_1]) if reverse
             else jnp.concatenate([fill_1, carry[:-1]]))
    return op(m, carry[:, None]).reshape(-1)[:n]


def _sort_count_phase(lk: jax.Array, rk: jax.Array) -> JoinPlanArrays:
    """Sort + the counting half of ReduceDuplicate (Mars pass 1).

    One sort of both sides' key columns together, ties broken by row with
    left rows first: a left row's first match is the number of right rows
    before it, and its match count runs to the right rows counted at its
    key group's end. Every field equals what two binary searches of the
    sorted left keys in the sorted right keys give, with no search loop.
    """
    n_l, n_r = lk.shape[0], rk.shape[0]
    n = n_l + n_r
    row = jnp.arange(n, dtype=jnp.int32)
    with jax.named_scope("sort"):
        cols = [jnp.concatenate([lk[:, c], rk[:, c]])
                for c in range(lk.shape[1])]
        *s_keys, s_row = _sort((*cols, row), len(cols) + 1)
    with jax.named_scope("count"):
        is_r = (s_row >= n_l).astype(jnp.int32)
        r_incl = _scan(lax.cumsum, jnp.add, is_r, 0)
        before = r_incl - is_r
        # the last row of each key group (the array's last row either way:
        # past the last group end, n_r is the count)
        group_end = jnp.stack(
            [c != jnp.roll(c, -1) for c in s_keys]).any(axis=0)
        upto = _scan(lax.cummin, jnp.minimum,
                     jnp.where(group_end, r_incl, n_r), n_r, reverse=True)
    with jax.named_scope("sort"):
        # back to sorted-left, then sorted-right order: a left row's place
        # is its merged position less the right rows before it
        dest = jnp.where(is_r == 1, n_l + before, row - before)
        _, order, lo, counts = _sort((dest, s_row, before, upto - before), 1)
        order_l, order_r = order[:n_l], order[n_l:] - n_l
        lo, counts = lo[:n_l], counts[:n_l]
    with jax.named_scope("count"):
        prefix = jnp.cumsum(counts, dtype=jnp.int32)
        total = prefix[-1] if n_l else jnp.int32(0)
    return JoinPlanArrays(order_l, order_r, lo, counts, prefix, total)


def expand_pairs_jnp(plan: JoinPlanArrays, capacity: int):
    """Inverse-prefix-sum expansion (pure-jnp reference path).

    For output slot t: left sorted-row i = first index with prefix[i] > t,
    offset within the group = t - (prefix[i] - counts[i]), right sorted-row
    j = lo[i] + offset. This is the dense, branch-free form of the paper's
    per-key cartesian product.
    """
    t = jnp.arange(capacity, dtype=jnp.int32)
    i = jnp.searchsorted(plan.prefix, t, side="right").astype(jnp.int32)
    i_c = jnp.minimum(i, plan.counts.shape[0] - 1)
    start = plan.prefix[i_c] - plan.counts[i_c]
    j = plan.lo[i_c] + (t - start)
    valid = t < plan.total
    li = plan.order_l[i_c]
    rj = plan.order_r[jnp.clip(j, 0, plan.order_r.shape[0] - 1)]
    return li, rj, valid


def expand_pairs(plan: JoinPlanArrays, capacity: int, use_kernel: bool = False):
    if use_kernel:
        from repro.kernels.pair_expand import ops as pe_ops

        i, off, valid = pe_ops.pair_expand(plan.prefix, plan.counts, capacity)
        j = plan.lo[i] + off
        li = plan.order_l[i]
        rj = plan.order_r[jnp.clip(j, 0, plan.order_r.shape[0] - 1)]
        return li, rj, valid
    return expand_pairs_jnp(plan, capacity)


def mr_join_plan(left: Relation, right: Relation) -> tuple[JoinPlanArrays, list[str]]:
    key_vars = shared_vars(left, right)
    if not key_vars:
        raise ValueError(
            f"cross join between {left.schema} and {right.schema}; use cross_join()"
        )
    with jax.named_scope("map"):
        lk, rk = _key_columns(left, right, key_vars)
    return _sort_count_phase(lk, rk), key_vars


def mr_join_count(left: Relation, right: Relation) -> jax.Array:
    """Mars pass 1: the exact result cardinality (jit-able, O(n log n))."""
    plan, _ = mr_join_plan(left, right)
    return plan.total


def mr_join(
    left: Relation,
    right: Relation,
    capacity: int,
    use_kernel: bool = False,
) -> tuple[Relation, jax.Array, jax.Array]:
    """Full Algorithm 1. Returns (result, exact_total, overflowed).

    Output schema: all left vars, then right vars not already bound.
    `capacity` is static; rows past `exact_total` are masked invalid. If
    exact_total > capacity the result is truncated and overflowed=True —
    the eager engine re-runs with a larger capacity (Mars two-pass).
    """
    plan, key_vars = mr_join_plan(left, right)
    with jax.named_scope("expand"):
        li, rj, valid = expand_pairs(plan, capacity, use_kernel=use_kernel)
        right_extra = [v for v in right.schema if v not in left.schema]
        out_schema = tuple(left.schema) + tuple(right_extra)
        l_cols = left.cols[li]
        r_cols = (
            right.project(right_extra).cols[rj]
            if right_extra
            else jnp.zeros((capacity, 0), jnp.int32)
        )
        cols = jnp.concatenate([l_cols, r_cols], axis=1)
        cols = jnp.where(valid[:, None], cols, 0)
        overflowed = plan.total > capacity
    return Relation(out_schema, cols, valid), plan.total, overflowed


def left_join(
    left: Relation,
    right: Relation,
    capacity: int,
    use_kernel: bool = False,
) -> tuple[Relation, jax.Array, jax.Array]:
    """OPTIONAL as Algorithm 1 plus unmatched-left padding.

    The first `capacity` output slots hold the inner-join result; the
    trailing `left.capacity` slots hold the left rows with no right match,
    their right-only columns set to the UNBOUND sentinel (so the padding
    part can never overflow). Returns (result, join_total, join_overflowed)
    where the total/overflow describe only the inner-join part — that is
    the bucket the engine calibrates and grows.
    """
    plan, _ = mr_join_plan(left, right)
    with jax.named_scope("expand"):
        li, rj, valid = expand_pairs(plan, capacity, use_kernel=use_kernel)
        right_extra = [v for v in right.schema if v not in left.schema]
        out_schema = tuple(left.schema) + tuple(right_extra)
        l_cols = left.cols[li]
        r_cols = (
            right.project(right_extra).cols[rj]
            if right_extra
            else jnp.zeros((capacity, 0), jnp.int32)
        )
        join_cols = jnp.where(
            valid[:, None], jnp.concatenate([l_cols, r_cols], axis=1), 0
        )
        # unmatched-left padding (the semijoin mask, inverted)
        unmatched = left.valid & ~_matched_left_mask(plan, left)
        pad = jnp.full(
            (left.capacity, len(right_extra)), UNBOUND, jnp.int32
        )
        pad_cols = jnp.concatenate([left.cols, pad], axis=1)
        cols = jnp.concatenate([join_cols, pad_cols], axis=0)
        valid_all = jnp.concatenate([valid, unmatched])
        overflowed = plan.total > capacity
    return Relation(out_schema, cols, valid_all), plan.total, overflowed


def cross_join(
    left: Relation, right: Relation, capacity: int
) -> tuple[Relation, jax.Array, jax.Array]:
    """Cartesian product for disconnected BGP components (no shared vars)."""
    n_r = right.capacity
    t = jnp.arange(capacity, dtype=jnp.int32)
    li, rj = t // n_r, t % n_r
    valid = left.valid[li] & right.valid[rj] & (t < left.capacity * n_r)
    cols = jnp.concatenate([left.cols[li], right.cols[rj]], axis=1)
    total = left.count() * right.count()
    # totals are exact but positions are not compacted: mask handles padding
    # interleaved with real rows; compact() can be applied afterwards.
    out = Relation(tuple(left.schema) + tuple(right.schema), cols, valid)
    return out, total, total > capacity


def compact(rel: Relation) -> Relation:
    """Stable-move valid rows to the front (static-shape compaction)."""
    order = jnp.argsort(~rel.valid, stable=True)
    return Relation(rel.schema, rel.cols[order], rel.valid[order])


def distinct(rel: Relation) -> Relation:
    """Mask duplicate rows (used for SELECT DISTINCT / projections)."""
    # Sort rows lexicographically with validity as the final tiebreak so all
    # valid copies of a row are adjacent and precede invalid (padding) copies.
    keys = ((~rel.valid).astype(jnp.int32),) + tuple(
        rel.cols[:, c] for c in reversed(range(rel.n_cols))
    )
    perm = jnp.lexsort(keys)
    cols_s = rel.cols[perm]
    valid_s = rel.valid[perm]
    same_as_prev = jnp.all(cols_s == jnp.roll(cols_s, 1, axis=0), axis=1)
    same_as_prev = same_as_prev.at[0].set(False)
    prev_valid = jnp.roll(valid_s, 1).at[0].set(False)
    keep = valid_s & ~(same_as_prev & prev_valid)
    inv = jnp.zeros_like(perm).at[perm].set(jnp.arange(perm.shape[0]))
    return Relation(rel.schema, rel.cols, keep[inv])


def _matched_left_mask(plan: JoinPlanArrays, left: Relation) -> jax.Array:
    """valid mask of left rows having >=1 right match, in buffer order
    (shared by semijoin_mask and left_join's unmatched padding)."""
    has = plan.counts > 0
    in_sorted_order = jnp.zeros(left.capacity, bool).at[plan.order_l].set(has)
    return left.valid & in_sorted_order


def semijoin_mask(left: Relation, right: Relation) -> jax.Array:
    """valid mask of left rows having >=1 match in right (for FILTER EXISTS)."""
    plan, _ = mr_join_plan(left, right)
    return _matched_left_mask(plan, left)


# -- FILTER masks and LIMIT/OFFSET (device-side, jit-able) -------------------

_NUMERIC_CMP = {
    "=": jnp.equal,
    "!=": jnp.not_equal,
    "<": jnp.less,
    "<=": jnp.less_equal,
    ">": jnp.greater,
    ">=": jnp.greater_equal,
}


def _numeric_of(col: jax.Array, num_vals: jax.Array) -> jax.Array:
    """Gather per-row numeric values; UNBOUND/non-numeric terms become NaN."""
    safe = jnp.clip(col, 0, num_vals.shape[0] - 1)
    return jnp.where(col >= 0, num_vals[safe], jnp.nan)


def _compare_mask(
    rel: Relation,
    lhs: str,
    op: str,
    kind: str,
    ref,
    consts_i: jax.Array,
    consts_f: jax.Array,
    num_vals: jax.Array,
) -> jax.Array:
    """One comparison as a boolean mask (validity handled by the caller).

      kind "var" — rhs is the variable named `ref`;
      kind "id"  — rhs is the term id `consts_i[ref]` (= / != by identity);
      kind "num" — rhs is the float `consts_f[ref]` (compared by value via
                   the dictionary's numeric table).
    SPARQL error semantics: an unbound operand, or a non-numeric term under
    a numeric comparison, fails the comparison — even for `!=`. With only
    `&&`/`||` above (no negation), error-as-false composes exactly like
    three-valued logic would.
    """
    a = rel.column(lhs)
    if kind == "num" or (kind == "var" and op in ("<", "<=", ">", ">=")):
        va = _numeric_of(a, num_vals)
        vb = (
            _numeric_of(rel.column(ref), num_vals)
            if kind == "var"
            else consts_f[ref]
        )
        ok = ~jnp.isnan(va) & ~jnp.isnan(vb)
        return ok & _NUMERIC_CMP[op](va, vb)
    # term-identity comparison (= / != on ids)
    b = rel.column(ref) if kind == "var" else consts_i[ref]
    bound = a != UNBOUND
    if kind == "var":
        bound = bound & (b != UNBOUND)
    eq = a == b
    return bound & (eq if op == "=" else ~eq)


def expr_mask(
    rel: Relation,
    expr: tuple,
    consts_i: jax.Array,
    consts_f: jax.Array,
    num_vals: jax.Array,
) -> jax.Array:
    """A plan_ir.FilterExpr as a composed device mask: comparisons at the
    leaves, `&`/`|` over ("and", ...) / ("or", ...) nodes."""
    tag = expr[0]
    if tag == "cmp":
        _, lhs, op, kind, ref = expr
        return _compare_mask(
            rel, lhs, op, kind, ref, consts_i, consts_f, num_vals
        )
    masks = [
        expr_mask(rel, c, consts_i, consts_f, num_vals) for c in expr[1]
    ]
    out = masks[0]
    for m in masks[1:]:
        out = (out & m) if tag == "and" else (out | m)
    return out


def filter_mask(
    rel: Relation,
    conds: tuple,
    consts_i: jax.Array,
    consts_f: jax.Array,
    num_vals: jax.Array,
) -> jax.Array:
    """Conjunction of filter expressions as a validity mask."""
    keep = rel.valid
    for expr in conds:
        keep = keep & expr_mask(rel, expr, consts_i, consts_f, num_vals)
    return keep


def union_all(rels: list[Relation], schema: tuple[str, ...]) -> Relation:
    """SPARQL UNION: multiset concatenation over an aligned schema.

    Columns a branch does not bind are filled with the UNBOUND sentinel
    (the decoder omits them; FILTER masks treat them as errors). Output
    capacity is the exact sum of branch capacities — never overflows.
    Duplicate solutions are preserved (multiset semantics); SELECT
    DISTINCT on top reuses the device `distinct` machinery to dedup.
    """
    cols_parts = []
    valid_parts = []
    for rel in rels:
        cols = [
            rel.column(v)
            if v in rel.schema
            else jnp.full((rel.capacity,), UNBOUND, jnp.int32)
            for v in schema
        ]
        cols_parts.append(jnp.stack(cols, axis=1))
        valid_parts.append(rel.valid)
    return Relation(
        tuple(schema),
        jnp.concatenate(cols_parts, axis=0),
        jnp.concatenate(valid_parts, axis=0),
    )


def slice_valid(rel: Relation, offset, limit) -> Relation:
    """LIMIT/OFFSET over the valid rows, in buffer order.

    `offset`/`limit` may be traced int scalars, so one compiled program
    serves every (offset, limit) combination of the same plan shape.
    """
    rank = jnp.cumsum(rel.valid.astype(jnp.int32))
    keep = rel.valid & (rank > offset) & (rank <= offset + limit)
    return Relation(rel.schema, rel.cols, keep)
