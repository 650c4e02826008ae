"""Mesh construction: the one place this repo builds a ``jax.sharding.Mesh``.

A function (not a module-level constant) so importing this module
never touches jax device state.

Every mesh here has ``Auto`` axes.  ``jax.make_mesh`` defaults to
``Explicit`` axes, under which sharding becomes part of each array's
type and the transforms' ``jax.grad``/``jit`` paths reject the
replicated scalars they carry.
"""

from __future__ import annotations

import jax


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with ``Auto`` axis types (see module doc)."""
    axes = tuple(axes)
    return jax.make_mesh(tuple(shape), axes, devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
