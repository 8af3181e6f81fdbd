"""Public sorted-segment-sum API with padding + size-based fallback."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import default_interpret
from repro.kernels.segment_reduce import kernel as _k
from repro.kernels.segment_reduce import ref as _ref

# Above this, the (S x d) one-hot accumulator would not fit VMEM; fall back.
_MAX_SEGMENTS = 4096


@functools.partial(jax.jit, static_argnames=("num_segments", "use_kernel"))
def sorted_segment_sum(data: jax.Array, ids: jax.Array, num_segments: int, *,
                       use_kernel: bool = True):
    """Sum rows of `data` by sorted segment id. ids >= num_segments drop."""
    n, d = data.shape
    if not use_kernel or num_segments > _MAX_SEGMENTS:
        return _ref.sorted_segment_sum(data, ids, num_segments)
    m = ((n + _k.BLOCK_N - 1) // _k.BLOCK_N) * _k.BLOCK_N
    pdata = jnp.zeros((m, d), data.dtype).at[:n].set(data)
    # out-of-range id => all-zero one-hot row => dropped (matches ref's drop)
    pids = jnp.full((m,), num_segments, jnp.int32).at[:n].set(ids.astype(jnp.int32))
    out = _k.sorted_segment_sum_pallas(pdata, pids, num_segments,
                                       interpret=default_interpret())
    return out.astype(data.dtype)
