"""Mesh construction with Auto axes.

``jax.make_mesh`` makes Explicit axes by default.  The repository's
shardings are written for Auto axes, where GSPMD propagates layouts
through gathers and reshapes, so every mesh is built through
``make_mesh`` here: it names ``AxisType.Auto`` for each axis unless the
caller passes ``axis_types`` itself.  ``shard_map`` is ``jax.shard_map``.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_mesh"]


def make_mesh(axis_shapes, axis_names, **kw):
    """``jax.make_mesh`` with ``AxisType.Auto`` on every axis by default."""
    if kw.get("axis_types") is None:
        kw["axis_types"] = (AxisType.Auto,) * len(tuple(axis_names))
    return jax.make_mesh(axis_shapes, axis_names, **kw)
