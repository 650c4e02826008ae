"""The comparisons that decide ``correct``: each gives one number, held to
the limit the configuration states for it."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


@jax.jit
def _gap(got, want):
    return jnp.max(jnp.abs(got - want)), jnp.max(jnp.abs(want))


def rel_gap(got, want) -> float:
    """The widest gap, max |got - want| over max |want|, on the device.
    NaN anywhere, or a zero reference, reads as infinity."""
    gap, scale = (float(v) for v in _gap(got, want))
    if not (math.isfinite(gap) and scale > 0):
        return math.inf
    return gap / scale


def layout_mismatches(arr, mesh, spec) -> int:
    """0 where ``arr`` lies on every device of ``mesh`` with the sharding
    ``spec`` names and the shard shape it implies; else 1."""
    if mesh is None or spec is None:
        return 0
    shards = arr.addressable_shards
    devices = {s.device for s in shards}
    shard_shape = arr.sharding.shard_shape(arr.shape)
    ok = (_padded(arr.sharding.spec, arr.ndim) == _padded(spec, arr.ndim)
          and devices == set(mesh.devices.flat)
          and {tuple(s.data.shape) for s in shards} == {tuple(shard_shape)}
          and math.prod(shard_shape) * mesh.devices.size == math.prod(
              arr.shape))
    return 0 if ok else 1


def _padded(spec, ndim: int) -> tuple:
    spec = tuple(spec)
    return spec + (None,) * (ndim - len(spec))


def verdict(numbers: dict, limits: dict) -> tuple[list, dict]:
    """(names over their limit, {name: {"value", "limit"}}); a number with
    no limit, or NaN, is over it."""
    table, over = {}, []
    for name, value in numbers.items():
        limit = limits.get(name)
        if limit is None or not value <= limit:
            over.append(name)
        # JSON has no infinity or NaN: such a reading is printed as text
        shown = value if math.isfinite(value) else repr(value)
        table[name] = {"value": shown, "limit": limit}
    return over, table
