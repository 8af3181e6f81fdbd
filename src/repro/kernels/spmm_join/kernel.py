"""Pallas kernels for the SpMM join reductions.

Layout: every compare tile is (BLOCK, 128) — BLOCK output rows on
sublanes against 128 comparison keys on lanes. The output rows arrive as
a (BLOCK, 1) column block per grid step; the comparison keys sit whole in
VMEM lane-dense, as (n / 128, 128), and a `fori_loop` walks them one
128-lane row at a time, so the program size does not grow with the
input. Per-row sums accumulate elementwise in (BLOCK, 128) tiles and are
reduced across lanes once per grid step. Every lane executes the same
data-independent schedule (no sort, no gather, no branches: this is the
whole point of the matrix backend).

`match_layout` additionally carries a per-right-row running match count
across grid steps, accumulated in place in its `cl` output block (every
grid step maps to block 0). TPU grids execute sequentially, so the
read-modify-write is well-defined — the same revisiting pattern as a
matmul's k-loop accumulator.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK = 128  # output rows per grid step
LANES = 128  # comparison keys per inner step


def _row_sums(tile: jax.Array) -> jax.Array:
    return jnp.sum(tile, axis=1, keepdims=True)


def _match_layout_kernel(lk_ref, lk_row_ref, rk_ref, counts_ref, first_ref,
                         b_ref, cl_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        cl_ref[...] = jnp.zeros(cl_ref.shape, jnp.int32)

    lk = lk_ref[...]  # (BLOCK, 1) this block's left keys
    zero = jnp.zeros((BLOCK, LANES), jnp.int32)

    def body(r, acc):
        counts, first, b_seen = acc
        rk = rk_ref[pl.ds(r, 1), :]  # (1, LANES) right keys
        seen = cl_ref[pl.ds(r, 1), :]  # their matches in earlier blocks
        eq = lk == rk
        counts = counts + jnp.where(eq, 1, 0)
        first = first + jnp.where(rk < lk, 1, 0)
        b_seen = b_seen + jnp.where(eq, seen, 0)
        cl_ref[pl.ds(r, 1), :] = seen + jnp.sum(
            jnp.where(eq, 1, 0), axis=0, keepdims=True)
        return counts, first, b_seen

    counts, first, b_seen = jax.lax.fori_loop(
        0, rk_ref.shape[0], body, (zero, zero, zero))
    counts = _row_sums(counts)
    # b[i] = counts[i] * (earlier left rows with the same key): those in
    # earlier blocks are b_seen's carry, those in this block a (BLOCK,
    # BLOCK) strictly-lower-triangular self-compare — no cumsum needed
    row = jax.lax.broadcasted_iota(jnp.int32, (BLOCK, BLOCK), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (BLOCK, BLOCK), 1)
    same_before = (lk == lk_row_ref[...]) & (col < row)
    occ = _row_sums(jnp.where(same_before, 1, 0))
    counts_ref[...] = counts
    first_ref[...] = _row_sums(first)
    b_ref[...] = counts * occ + _row_sums(b_seen)


def _sort_ranks_kernel(own_ref, keys_ref, out_ref):
    own = own_ref[...]  # (BLOCK, 1) this block's keys
    j = pl.program_id(0) * BLOCK + jax.lax.broadcasted_iota(
        jnp.int32, (BLOCK, LANES), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)

    def body(r, acc):
        kc = keys_ref[pl.ds(r, 1), :]  # (1, LANES)
        before = (r * LANES + lane) < j
        hit = (kc < own) | ((kc == own) & before)
        return acc + jnp.where(hit, 1, 0)

    acc = jax.lax.fori_loop(0, keys_ref.shape[0], body,
                            jnp.zeros((BLOCK, LANES), jnp.int32))
    out_ref[...] = _row_sums(acc)


def _column_spec():
    return pl.BlockSpec((BLOCK, 1), lambda i: (i, 0))


def _resident_spec(shape):
    return pl.BlockSpec(shape, lambda i: (0, 0))


@functools.partial(jax.jit, static_argnames=("interpret",))
def match_layout_pallas(left_keys: jax.Array, right_keys: jax.Array, *,
                        interpret: bool = False):
    """Per-left-row (counts, first, b) and per-right-row cl; inputs
    pre-padded to BLOCK / LANES. The right pad value must neither equal
    nor sit below any real left key, so padded right rows count into no
    sum; padded LEFT rows come after every real row, so their eq
    contributions to cl (none, by pad-value choice) and to later rows'
    b (none — there are no later rows) are nil."""
    n_left, n_right = left_keys.shape[0], right_keys.shape[0]
    assert n_left % BLOCK == 0 and n_right % LANES == 0
    rk = right_keys.reshape(n_right // LANES, LANES)
    counts, first, b, cl = pl.pallas_call(
        _match_layout_kernel,
        grid=(n_left // BLOCK,),
        in_specs=[
            _column_spec(),
            pl.BlockSpec((1, BLOCK), lambda i: (0, i)),
            _resident_spec(rk.shape),
        ],
        out_specs=[_column_spec()] * 3 + [_resident_spec(rk.shape)],
        out_shape=[jax.ShapeDtypeStruct((n_left, 1), jnp.int32)] * 3
        + [jax.ShapeDtypeStruct(rk.shape, jnp.int32)],
        interpret=interpret,
    )(left_keys.reshape(n_left, 1), left_keys.reshape(1, n_left), rk)
    return (counts.reshape(n_left), first.reshape(n_left),
            b.reshape(n_left), cl.reshape(n_right))


@functools.partial(jax.jit, static_argnames=("interpret",))
def sort_ranks_pallas(keys: jax.Array, *,
                      interpret: bool = False) -> jax.Array:
    """Per-row stable sorted position of its key; input pre-padded to
    BLOCK (the pad value must not be below any real key — padded rows sit
    at the tail of the ranking and real rows' ranks are unaffected)."""
    n = keys.shape[0]
    assert n % BLOCK == 0 and BLOCK % LANES == 0
    lanes = keys.reshape(n // LANES, LANES)
    out = pl.pallas_call(
        _sort_ranks_kernel,
        grid=(n // BLOCK,),
        in_specs=[_column_spec(), _resident_spec(lanes.shape)],
        out_specs=_column_spec(),
        out_shape=jax.ShapeDtypeStruct((n, 1), jnp.int32),
        interpret=interpret,
    )(keys.reshape(n, 1), lanes)
    return out.reshape(n)
