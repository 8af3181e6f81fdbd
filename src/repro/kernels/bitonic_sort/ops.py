"""Public sort API: padding, power-of-two handling, large-N fallback."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import default_interpret
from repro.kernels.bitonic_sort import kernel as _k
from repro.kernels.bitonic_sort import ref as _ref

_MAX_KERNEL_N = 2**19  # ~4 MB keys+vals in VMEM, well under 16 MB
_PAD_KEY = np.int32(2**31 - 1)


def _next_pow2(n: int) -> int:
    return 1 << max(0, (n - 1).bit_length())


@functools.partial(jax.jit, static_argnames=("use_kernel",))
def sort_pairs(keys: jax.Array, vals: jax.Array, *, use_kernel: bool = True):
    """Sort (keys, vals) by key ascending; any length, int32.

    Padding keys (INT32_MAX) sort to the end and are sliced off. NOTE: the
    bitonic network is not stable — equal keys may permute their payloads
    (callers in this codebase never rely on stability).
    """
    n = keys.shape[0]
    if not use_kernel or n > _MAX_KERNEL_N or n < 2:
        return _ref.sort_pairs(keys, vals)
    m = _next_pow2(n)
    pk = jnp.full((m,), _PAD_KEY, jnp.int32).at[:n].set(keys.astype(jnp.int32))
    pv = jnp.zeros((m,), jnp.int32).at[:n].set(vals.astype(jnp.int32))
    sk, sv = _k.bitonic_sort_pairs(pk, pv, interpret=default_interpret())
    return sk[:n], sv[:n]


def argsort_i32(keys: jax.Array, **kw) -> jax.Array:
    """Permutation sorting `keys` ascending (payload = row index)."""
    n = keys.shape[0]
    _, order = sort_pairs(keys, jnp.arange(n, dtype=jnp.int32), **kw)
    return order
