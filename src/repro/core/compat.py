"""JAX API shims: the one place that spells the version-sensitive symbols.

The library targets the installed JAX (0.9).  A handful of symbols have
moved or changed signature across releases; every use of them in this
repo MUST go through this module so that a future move lands in one
place:

* ``shard_map`` — ``jax.shard_map`` with ``check_vma`` and the
  ``axis_names`` set of manual axes.
* ``make_mesh`` — every axis explicitly ``jax.sharding.AxisType.Auto``.
* ``set_mesh`` — ``jax.set_mesh``.
* ``enable_x64`` — ``jax.enable_x64(True)`` as a context manager (the
  ``jax.experimental.enable_x64`` spelling is gone).
* ``tracing`` — whether a transformation is tracing the caller.
* ``tree_map`` & friends — aliases of ``jax.tree.*``; calling
  ``jax.tree.*`` directly elsewhere in the tree is fine.

Nothing here imports anything heavier than ``jax`` itself.
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Sequence, Set

import jax

__all__ = [
    "make_mesh", "set_mesh", "enable_x64", "tracing",
    "shard_map", "scan", "while_loop", "tree_map", "tree_flatten",
    "tree_unflatten", "tree_leaves", "tree_structure",
]


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str], *,
              devices=None) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with every axis explicitly ``Auto``."""
    kwargs = {"devices": devices} if devices is not None else {}
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names),
                         axis_types=(jax.sharding.AxisType.Auto,)
                         * len(tuple(axis_names)),
                         **kwargs)


def set_mesh(mesh: jax.sharding.Mesh):
    """Context manager activating ``mesh``."""
    return jax.set_mesh(mesh)


def enable_x64():
    """Context manager enabling 64-bit types for the enclosed block."""
    return jax.enable_x64(True)


def tracing() -> bool:
    """Whether a JAX transformation (an enclosing ``jit``, ``grad``,
    ``vmap``) is tracing the caller."""
    return not jax.core.trace_ctx.is_top_level()


def shard_map(f: Callable, *, mesh: Any, in_specs: Any, out_specs: Any,
              check_vma: bool = False,
              axis_names: Optional[Set[str]] = None) -> Callable:
    """``jax.shard_map``; ``axis_names`` is the set of mesh axes the
    region is manual over (``None`` = all of them)."""
    kwargs = {}
    if axis_names is not None:
        kwargs["axis_names"] = set(axis_names)
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma, **kwargs)


# -- structured control flow -----------------------------------------------
#
# ``lax.scan``/``lax.while_loop`` are the symbols ``LPFContext
# .compile_loop`` and the fused decode loop hang off — routed through
# here so a future signature change has one place to land.

def scan(f, init, xs, length=None):
    """``lax.scan`` (body traced once; per-iteration work compiles into
    ONE XLA ``While`` op instead of a Python-dispatched call per step)."""
    return jax.lax.scan(f, init, xs, length=length)


def while_loop(cond_fun, body_fun, init_val):
    """``lax.while_loop`` — same single-trace contract as :func:`scan`."""
    return jax.lax.while_loop(cond_fun, body_fun, init_val)


# -- pytree helpers --------------------------------------------------------

tree_map = jax.tree.map
tree_flatten = jax.tree.flatten
tree_unflatten = jax.tree.unflatten
tree_leaves = jax.tree.leaves
tree_structure = jax.tree.structure
