"""Pallas TPU kernels for the MapSQ hot spots.

Each kernel package has:
  kernel.py — pl.pallas_call + explicit BlockSpec VMEM tiling (TPU target)
  ops.py    — jit'd public wrapper (padding, size bounds, interpret mode
              decided by the platform alone)
  ref.py    — pure-jnp oracle used by tests and by CPU-only paths

Kernels are validated in interpret mode on the CPU and compiled for the
TPU by tests/test_tpu_compile.py (Mosaic refuses what interpret mode
accepts: in-kernel cumsum, 1-D gathers, unsupported shape casts). They
are written against TPU constraints: lane width 128, sublane 8, VMEM
~16 MB/core, branch-free data-independent schedules.
"""

import jax


def default_interpret() -> bool:
    """Interpret Pallas on non-TPU backends so kernels run everywhere; on
    the TPU a kernel always compiles (there is no override)."""
    return jax.default_backend() != "tpu"
