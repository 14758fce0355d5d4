"""Mesh construction shared by the runtime, launchers and tests.

``jax.make_mesh`` defaults to Explicit axis types; the runtime's shard_maps
and ``NamedSharding`` placements are written for Auto axes, so every mesh is
built here with Auto axis types.
"""

from __future__ import annotations

import jax


def make_mesh(axis_shapes, axis_names, devices=None):
    """``jax.make_mesh`` with Auto axis types (optionally over ``devices``)."""
    return jax.make_mesh(
        axis_shapes, axis_names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names),
        devices=devices)
