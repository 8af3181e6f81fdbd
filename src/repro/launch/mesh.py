"""Production mesh builders. A FUNCTION, not a module constant, so importing
this module never touches jax device state (device count locks at first use).
"""
from __future__ import annotations

import jax

from repro.core.compat import make_mesh


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod.

    Axes: "data" = batch/shuffle parallel, "model" = tensor/expert/sequence
    parallel, "pod" = the slow inter-pod axis (data-parallel across pods;
    the hierarchical shuffle routes over it exactly once).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1,
                    pod: int = 0) -> jax.sharding.Mesh:
    """Small mesh over however many devices this host actually has
    (smoke tests, examples, CI)."""
    if pod:
        return make_mesh((pod, data, model), ("pod", "data", "model"))
    return make_mesh((data, model), ("data", "model"))
