"""The sharding API as this tree spells it (jax 0.9).

One spelling of each call the rest of the tree uses:

- :func:`make_mesh` / :func:`mesh_from_devices` — meshes whose axes are all
  ``AxisType.Auto`` (the Pier steps make the group axes manual themselves,
  inside :func:`shard_map`).
- :func:`shard_map` — partial-manual ``jax.shard_map``: ``axis_names``
  manual, the rest auto, with the varying-mesh-axes (VMA) check on.
- :func:`mark_varying` — ``lax.pcast(..., to="varying")`` over only the
  axes a value does not already vary over.
- :func:`all_gather_invariant` — the all-gather whose result is typed
  invariant over the gathered axis.
- :func:`mesh_context` — ``jax.set_mesh``.
"""

from __future__ import annotations

from typing import Callable, Sequence, Set, Tuple

import jax
from jax.sharding import AxisType, Mesh


def make_mesh(shape: Sequence[int], axes: Sequence[str]) -> Mesh:
    """``jax.make_mesh`` with all-auto axis types."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(shape))


def mesh_from_devices(devices, axes: Sequence[str]) -> Mesh:
    """``Mesh(devices, axes)`` with all-auto axis types."""
    return Mesh(devices, tuple(axes), axis_types=(AxisType.Auto,) * len(axes))


def shard_map(
    f: Callable,
    *,
    mesh: Mesh,
    in_specs,
    out_specs,
    axis_names: Set[str],
):
    """Partial-manual shard_map: ``axis_names`` manual, the rest auto."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, axis_names=set(axis_names))


def mark_varying(x, axis_names: Tuple[str, ...]):
    """Mark the pytree ``x`` varying over ``axis_names``.

    ``pcast(to="varying")`` accepts only axes a value is invariant over, so
    each leaf is cast over just the axes missing from its VMA type (a no-op
    where it already varies over all of them).
    """
    def cast(leaf):
        missing = tuple(a for a in axis_names
                        if a not in jax.typeof(leaf).vma)
        return jax.lax.pcast(leaf, missing, to="varying") if missing else leaf

    return jax.tree.map(cast, x)


def all_gather_invariant(x, axis_name: str):
    """Stack ``x`` from every index of ``axis_name``, in axis-index order.

    Every device ends with the same stack, so the result is typed invariant
    over ``axis_name`` (``lax.all_gather`` types it varying). jax 0.9 keeps
    this primitive under ``jax._src``.
    """
    from jax._src.lax.parallel import all_gather_invariant as _agi

    return _agi(x, axis_name)


def mesh_context(mesh: Mesh):
    """Context manager putting ``mesh`` in scope for sharding constraints."""
    return jax.set_mesh(mesh)
