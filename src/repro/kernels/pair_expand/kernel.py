"""ReduceDuplicate pair expansion — the MapSQ cartesian product, dense.

The paper's GPU ReduceDuplicate assigns one thread per output pair. The TPU
form inverts the inclusive prefix sum of per-left-row match counts with
dense reductions instead of a search: because the prefix is
non-decreasing, output slot t belongs to left row

    i(t)     = |{i : prefix[i] <= t}|
    start(t) = max({prefix[i] : prefix[i] <= t} | {0})   (= prefix[i - 1])

so every slot is one compare-and-reduce over the prefix array — no gather,
no branch, the same schedule on every lane regardless of join skew, which
is exactly the property the paper's flag/sort machinery buys on the GPU.

Tiling: output slots run down the sublanes, BLOCK per grid step, as
(BLOCK, 1) column blocks; the prefix sits whole in VMEM lane-dense, as
(n / 128, 128), and a `fori_loop` walks it one 128-lane row at a time
(one int32 word per left row — 4 MB covers a million-row shard).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK = 128  # output slots per grid step
LANES = 128  # prefix entries per inner step
PAD = 2**31 - 1  # prefix padding: above every slot index


def _pair_expand_kernel(prefix_ref, out_i_ref, out_off_ref, out_valid_ref,
                        *, n_left: int):
    t = pl.program_id(0) * BLOCK + jax.lax.broadcasted_iota(
        jnp.int32, (BLOCK, LANES), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)

    def body(r, acc):
        rows, start = acc
        p = prefix_ref[pl.ds(r, 1), :]  # (1, LANES); PAD past n_left
        le = p <= t
        # the last row's prefix never opens a slot range: slots past the
        # total clamp to the last row, as the reference does
        below_last = (r * LANES + lane) < n_left - 1
        rows = rows + jnp.where(le, 1, 0)
        start = jnp.maximum(start, jnp.where(le & below_last, p, 0))
        return rows, start

    zero = jnp.zeros((BLOCK, LANES), jnp.int32)
    rows, start = jax.lax.fori_loop(0, prefix_ref.shape[0], body,
                                    (zero, zero))
    last_r, last_l = divmod(n_left - 1, LANES)
    total = prefix_ref[last_r:last_r + 1, last_l:last_l + 1]  # (1, 1)
    slot = t[:, :1]
    out_i_ref[...] = jnp.minimum(jnp.sum(rows, axis=1, keepdims=True),
                                 n_left - 1)
    out_off_ref[...] = slot - jnp.max(start, axis=1, keepdims=True)
    out_valid_ref[...] = jnp.where(slot < total, 1, 0)


@functools.partial(jax.jit, static_argnames=("capacity", "interpret"))
def pair_expand_pallas(prefix: jax.Array, capacity: int, *,
                       interpret: bool = False):
    """prefix -> (left_sorted_row, offset_in_group, valid) per slot."""
    n_left = prefix.shape[0]
    assert capacity % BLOCK == 0
    n_pad = -(-n_left // LANES) * LANES
    lanes = jnp.pad(prefix, (0, n_pad - n_left), constant_values=PAD)
    lanes = lanes.reshape(n_pad // LANES, LANES)
    column = pl.BlockSpec((BLOCK, 1), lambda i: (i, 0))
    out = pl.pallas_call(
        functools.partial(_pair_expand_kernel, n_left=n_left),
        grid=(capacity // BLOCK,),
        in_specs=[pl.BlockSpec(lanes.shape, lambda i: (0, 0))],
        out_specs=[column] * 3,
        out_shape=[jax.ShapeDtypeStruct((capacity, 1), jnp.int32)] * 3,
        interpret=interpret,
    )(lanes)
    return tuple(o.reshape(capacity) for o in out)
