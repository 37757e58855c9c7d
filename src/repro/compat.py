"""The one mesh builder every mesh in this repo goes through.

``jax.make_mesh`` gives Explicit axes by default, under which a
``shard_map`` or a gather called outside ``jax.set_mesh`` is refused; the
repo's meshes are Auto-axis meshes built by :func:`make_mesh`.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_mesh"]


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with Auto axis types (over ``devices`` if given)."""
    kw = {} if devices is None else {"devices": devices}
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         **kw)
