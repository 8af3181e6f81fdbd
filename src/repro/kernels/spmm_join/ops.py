"""Public SpMM-join reduction API with padding + fallback.

Padding values: the left side pads with INVALID_LEFT and the right side
with INVALID_RIGHT (the relation sentinels), which by construction never
equal a real dictionary id or dense rank — padded right rows therefore
contribute no spurious matches, and padded rows' own outputs are sliced
off before returning.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import default_interpret
from repro.kernels.spmm_join import kernel as _k
from repro.kernels.spmm_join import ref as _ref

_PAD_LEFT = 2**31 - 1  # relation.INVALID_LEFT
_PAD_RIGHT = 2**31 - 2  # relation.INVALID_RIGHT


def _pad_to(x: jax.Array, multiple: int, value: int) -> jax.Array:
    n = x.shape[0]
    n_pad = ((n + multiple - 1) // multiple) * multiple
    return jnp.pad(x, (0, n_pad - n), constant_values=jnp.int32(value))


@functools.partial(jax.jit, static_argnames=("use_kernel",))
def match_layout(left_keys: jax.Array, right_keys: jax.Array, *,
                 use_kernel: bool = True):
    """(counts[i], first[i], b[i], cl[j]): the full output layout of the
    join, from one dense eq/lt pass (see ref.match_layout).

    Right-side padding with INVALID_RIGHT is sound for every sum: no
    valid left key reaches the sentinels, so padded rows are neither
    equal to nor below any real left key. Left-side padding with
    INVALID_LEFT matches nothing on the right (so cl is clean) and sits
    after every real row (so no real row's b sees it)."""
    if not use_kernel or left_keys.shape[0] < 2 or right_keys.shape[0] < 2:
        return _ref.match_layout(left_keys, right_keys)
    lp = _pad_to(left_keys.astype(jnp.int32), _k.BLOCK, _PAD_LEFT)
    rp = _pad_to(right_keys.astype(jnp.int32), _k.LANES, _PAD_RIGHT)
    counts, first, b, cl = _k.match_layout_pallas(
        lp, rp, interpret=default_interpret())
    n_l, n_r = left_keys.shape[0], right_keys.shape[0]
    return counts[:n_l], first[:n_l], b[:n_l], cl[:n_r]


@functools.partial(jax.jit, static_argnames=("use_kernel",))
def sort_ranks(keys: jax.Array, *, use_kernel: bool = True) -> jax.Array:
    """rank[j] = the row's stable sorted position (a permutation of 0..n-1).

    Padding with INVALID_LEFT (int32 max) is sound for either side's keys:
    no real key exceeds it, and rows EQUAL to it (invalid-left sentinels)
    precede the pads in buffer order, so stability keeps every real row's
    rank inside 0..n-1 — padded rows rank strictly at the tail."""
    if not use_kernel or keys.shape[0] < 2:
        return _ref.sort_ranks(keys)
    kp = _pad_to(keys.astype(jnp.int32), _k.BLOCK, _PAD_LEFT)
    out = _k.sort_ranks_pallas(kp, interpret=default_interpret())
    return out[: keys.shape[0]]
