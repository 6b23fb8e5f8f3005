"""Mesh and sharding helpers shared by every meshed code path.

* ``make_mesh(shape, axes)`` — a mesh over the local devices with Auto
  axis types (explicit-sharding mode is never used here);
* ``specs_to_shardings(tree, mesh=...)`` — maps a PartitionSpec pytree to
  NamedShardings for ``jit``'s ``in_shardings``.

Everything else is spelled the jax way at the call site: ``jax.set_mesh``,
``jax.sharding.get_abstract_mesh`` and ``jax.shard_map``.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, NamedSharding
from jax.sharding import PartitionSpec as P


def make_mesh(shape, axes) -> jax.sharding.Mesh:
    """Auto-axis mesh over the local devices."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def specs_to_shardings(tree, *, mesh=None):
    """PartitionSpec pytree -> NamedSharding pytree against ``mesh``.

    ``mesh`` defaults to the active mesh.  None leaves mean "replicated"
    (NamedSharding(mesh, P())).
    """
    if mesh is None:
        mesh = jax.sharding.get_abstract_mesh()
        if mesh.empty:
            raise ValueError("specs_to_shardings needs a mesh (none active)")
    # an active mesh may come back abstract; NamedSharding wants the
    # concrete one
    concrete = getattr(mesh, "_concrete_mesh", None) or mesh
    return jax.tree.map(
        lambda s: NamedSharding(concrete, s if s is not None else P()),
        tree,
        is_leaf=lambda x: x is None or isinstance(x, P),
    )
