"""Public pair-expand API with padding + fallback."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import default_interpret
from repro.kernels.pair_expand import kernel as _k
from repro.kernels.pair_expand import ref as _ref


@functools.partial(jax.jit, static_argnames=("capacity", "use_kernel"))
def pair_expand(prefix: jax.Array, counts: jax.Array, capacity: int, *,
                use_kernel: bool = True):
    """For each output slot: (sorted-left row, offset within group, valid).
    `prefix` is the inclusive prefix sum of `counts`; the kernel needs
    only the prefix."""
    if not use_kernel or prefix.shape[0] < 2:
        return _ref.pair_expand(prefix, counts, capacity)
    cap = ((capacity + _k.BLOCK - 1) // _k.BLOCK) * _k.BLOCK
    i, off, valid = _k.pair_expand_pallas(
        prefix.astype(jnp.int32), cap, interpret=default_interpret())
    return i[:capacity], off[:capacity], valid[:capacity].astype(bool)
