"""The matrix join backend: MapSQ's equi-join as masked SpMM reductions.

Where Algorithm 1 (core/mr_join.py) realises the join as Map -> Sort ->
ReduceDuplicate, this backend — the gSMat/gSmart reformulation — never
sorts. The Map phase is shared (sentinel-tagged key extraction); then
dense masked reductions (kernels/spmm_join) drive the whole join:

  counts[i], first[i], b[i], cl[j]  <- match_layout: ONE eq/lt tile pass
  pos[j]    = stable sorted rank of rk[j]  (less-than + earlier-equal sum,
              right side only — the small input)

Left row i's outputs start at slot  start[i] = Pex[first[i]] + b[i],
where Pex is the exclusive prefix of cl in sorted-right order: slots for
all smaller keys, plus slots claimed by earlier same-key left rows. The
left side is never sorted OR ranked — zero-count rows occupy zero slots,
and every matching key exists on the right, so the right side's order
carries all the information. The expansion scatters the slot-monotone
code first[i]*n_l + i at start[i] and running-maxes it across slots to
recover each slot's left row; the right row is then a gather into the
sorted-right inverse permutation at first + occurrence rank.

The dense compares cost O(n_l * n_r) tiles, which is why the optimizer
only picks this backend when selectivity x skew says the output is within
a constant factor of the dense product — exactly where the MR backend's
two argsorts are pure overhead. Match ordering is IDENTICAL to mr_join's
(left rows in stable key order, then right buffer order within a key),
so the two backends are bit-compatible, not just set-equal.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.mr_join import _map_phase
from repro.core.relation import UNBOUND, Relation, shared_vars
from repro.kernels.spmm_join import ops as spmm_ops


def _match_arrays(left: Relation, right: Relation, use_kernel: bool):
    key_vars = shared_vars(left, right)
    if not key_vars:
        raise ValueError(
            f"cross join between {left.schema} and {right.schema}; "
            "use cross_join()"
        )
    with jax.named_scope("map"):
        l_key, r_key = _map_phase(left, right, key_vars)
    with jax.named_scope("layout"):
        counts, first, b, cl = spmm_ops.match_layout(
            l_key, r_key, use_kernel=use_kernel
        )
        pos_r = spmm_ops.sort_ranks(r_key, use_kernel=use_kernel)
    return counts, first, b, cl, pos_r


def _expand_gather(counts, first, b, cl, pos_r, capacity: int):
    """Gather each output slot's (left row, right row) pair.

    Emission order is bit-identical to mr_join's (left rows in stable key
    order, right buffer order within a key) without ever ordering the
    left side: start[i] = Pex[first[i]] + b[i] places each matching row's
    slot range directly, and the slot-monotone code first[i]*n_l + i —
    strictly increasing along the emission order, decodable with one mod
    — is scattered at range starts and cummax-filled to invert the
    mapping. Everything per-slot is a gather or a scan; the only scatters
    are n_r- and n_l-sized (tiny next to capacity).
    """
    n_l, n_r = counts.shape[0], pos_r.shape[0]
    rows = jnp.arange(n_l, dtype=jnp.int32)
    # right side in stable key order: j_at[pos_r[j]] = j (no argsort)
    j_at = jnp.zeros((n_r,), jnp.int32).at[pos_r].set(
        jnp.arange(n_r, dtype=jnp.int32)
    )
    if n_r:
        cl_sorted = cl[j_at]
        pex = jnp.cumsum(cl_sorted, dtype=jnp.int32) - cl_sorted
        before_key = pex[jnp.clip(first, 0, n_r - 1)]
    else:
        before_key = jnp.zeros_like(first)
    start = before_key + b
    total = jnp.sum(counts, dtype=jnp.int32)
    # scatter each matching row's code at its range start; cummax fills
    # the whole range (codes increase along slots, so later starts win)
    idx = jnp.where(counts > 0, start, capacity)  # zero-count rows: drop
    marks = jnp.zeros((capacity,), jnp.int32).at[idx].set(
        first * n_l + rows, mode="drop"
    )
    li = jax.lax.cummax(marks) % max(n_l, 1)
    k = jnp.arange(capacity, dtype=jnp.int32)
    r_k = k - start[li]  # occurrence rank of slot k within its left row
    rj = j_at[jnp.clip(first[li] + r_k, 0, max(n_r - 1, 0))]
    valid = k < total
    return li, rj, valid, total


def _joined_cols(left, right, li, rj, valid, capacity):
    right_extra = [v for v in right.schema if v not in left.schema]
    out_schema = tuple(left.schema) + tuple(right_extra)
    l_cols = left.cols[li]
    r_cols = (
        right.project(right_extra).cols[rj]
        if right_extra
        else jnp.zeros((capacity, 0), jnp.int32)
    )
    cols = jnp.concatenate([l_cols, r_cols], axis=1)
    return out_schema, right_extra, jnp.where(valid[:, None], cols, 0)


def matrix_join(
    left: Relation,
    right: Relation,
    capacity: int,
    use_kernel: bool = False,
) -> tuple[Relation, jax.Array, jax.Array]:
    """Matrix-backend equi-join; same contract and output schema as
    mr_join: (result, exact_total, overflowed), schema = left vars then
    right vars not already bound, rows past capacity truncated exactly."""
    counts, first, b, cl, pos_r = _match_arrays(left, right, use_kernel)
    with jax.named_scope("expand"):
        li, rj, valid, total = _expand_gather(
            counts, first, b, cl, pos_r, capacity
        )
        out_schema, _, cols = _joined_cols(
            left, right, li, rj, valid, capacity
        )
    return Relation(out_schema, cols, valid), total, total > capacity


def matrix_left_join(
    left: Relation,
    right: Relation,
    capacity: int,
    use_kernel: bool = False,
) -> tuple[Relation, jax.Array, jax.Array]:
    """OPTIONAL on the matrix backend; same layout as mr_join.left_join:
    `capacity` inner-join slots, then left.capacity unmatched-left padding
    slots with right-only columns UNBOUND. The unmatched mask falls out of
    the counts vector directly (counts are already in left buffer order —
    no sort to invert, unlike the MR backend's semijoin scatter-back)."""
    counts, first, b, cl, pos_r = _match_arrays(left, right, use_kernel)
    with jax.named_scope("expand"):
        li, rj, valid, total = _expand_gather(
            counts, first, b, cl, pos_r, capacity
        )
        out_schema, right_extra, join_cols = _joined_cols(
            left, right, li, rj, valid, capacity
        )
        unmatched = left.valid & (counts == 0)
        pad = jnp.full(
            (left.capacity, len(right_extra)), UNBOUND, jnp.int32
        )
        pad_cols = jnp.concatenate([left.cols, pad], axis=1)
        cols = jnp.concatenate([join_cols, pad_cols], axis=0)
        valid_all = jnp.concatenate([valid, unmatched])
    return Relation(out_schema, cols, valid_all), total, total > capacity
