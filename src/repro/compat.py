"""JAX API shims, resolved once for the installed JAX series (0.9).

The rest of the codebase (and the tests) import these stable names:

* ``enable_x64`` -- the x64 context manager (``jax.enable_x64``).
* ``make_mesh(shape, axis_names)`` -- ``jax.make_mesh`` with ``Auto``
  axis types on every axis.
* ``shard_map`` -- ``jax.shard_map``.
* ``compiled_cost_analysis`` -- ``Compiled.cost_analysis()`` as a plain
  dict.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

enable_x64 = jax.enable_x64
shard_map = jax.shard_map


def make_mesh(axis_shapes, axis_names, *, devices=None):
    """``jax.make_mesh`` with ``Auto`` axis types."""
    return jax.make_mesh(axis_shapes, axis_names,
                         axis_types=(AxisType.Auto,) * len(axis_names),
                         devices=devices)


def compiled_cost_analysis(compiled) -> dict:
    """Cost analysis of a compiled executable as a flat dict."""
    return dict(compiled.cost_analysis())
