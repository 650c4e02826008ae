"""Mesh construction: the one place this repo builds a ``jax.sharding.Mesh``.

Functions (not module-level constants) so importing this module never
touches jax device state; ``dryrun.py`` sets the 512-placeholder-device
XLA flag before calling them.

Every mesh here has ``Auto`` axes.  ``jax.make_mesh`` defaults to
``Explicit`` axes, under which sharding becomes part of each array's
type and the transforms' ``jax.grad``/``jit`` paths reject the
replicated scalars they carry.
"""

from __future__ import annotations

import math

import jax


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with ``Auto`` axis types (see module doc)."""
    axes = tuple(axes)
    return jax.make_mesh(tuple(shape), axes, devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(model: int = 0):
    """Best-effort mesh from whatever devices exist (tests / smoke runs)."""
    n = len(jax.devices())
    if model <= 0:
        model = 1
        for cand in (2, 4, 8, 16):
            if n % cand == 0 and cand <= n:
                model = cand
    return make_mesh((n // model, model), ("data", "model"))


def fft_mesh_axes(mesh) -> tuple:
    """Pencil (Py, Pz) communicator axes on a production mesh: the pod axis
    folds into the Y communicator (DESIGN.md §2)."""
    names = mesh.axis_names
    if "pod" in names:
        return (("pod", "data"), "model")
    return ("data", "model")
