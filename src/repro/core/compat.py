"""Mesh / shard_map helpers, one spelling each for the installed jax.

Everything that builds or enters a mesh goes through here, so the mesh
axis types and the shard_map flags are decided in one place.
"""
from __future__ import annotations

from collections.abc import Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str],
              devices=None) -> jax.sharding.Mesh:
    """`jax.make_mesh` with `Auto` axes.

    jax's default is `Explicit` axes, under which an eager op on a
    row-sharded array (a gather, a sort) must name its output sharding;
    the engine's host-driven calibration runs such ops eagerly and lets
    XLA choose, which `Auto` axes allow."""
    return jax.make_mesh(
        tuple(shape), tuple(axes),
        axis_types=(AxisType.Auto,) * len(axes), devices=devices,
    )


def shard_map(f, mesh=None, in_specs=None, out_specs=None,
              check_vma: bool = False):
    """`jax.shard_map`; `mesh=None` means the ambient mesh (`set_mesh`)."""
    kwargs = {} if mesh is None else {"mesh": mesh}
    return jax.shard_map(
        f, in_specs=in_specs, out_specs=out_specs, check_vma=check_vma,
        **kwargs,
    )


def axis_size(axis_name) -> int:
    """Static size of a mapped mesh axis, from inside shard_map."""
    return jax.lax.axis_size(axis_name)


def ambient_mesh():
    """The (abstract) mesh made ambient by `set_mesh`."""
    return jax.sharding.get_abstract_mesh()


def set_mesh(mesh):
    """Context manager making `mesh` the ambient mesh (so bare
    PartitionSpecs in `with_sharding_constraint` resolve against it)."""
    return jax.set_mesh(mesh)
