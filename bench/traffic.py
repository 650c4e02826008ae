"""The one generator of inputs: fields drawn from ``--seed`` on the device,
as a traffic file's parameters say.

A traffic file (``bench/traffic/<cell>.json``) names the step kind and
its parameters; the input keys read here are ``input.dist`` (``"normal"``:
independent standard normals, real and imaginary parts alike) and, for
step kinds that cycle through a pool of inputs, ``pool``.  Any whole
number is a seed: it is hashed to a 64-bit key, so seeds past 2**32 do
not wrap onto small ones.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

DISTS = ("normal",)


def _words(seed: int, n: int) -> np.ndarray:
    return np.random.SeedSequence(int(seed) & ((1 << 128) - 1)
                                  ).generate_state(n, dtype=np.uint32)


def seed_key(seed: int):
    """A JAX key from any whole number."""
    return jax.random.wrap_key_data(jnp.asarray(_words(seed, 2)),
                                    impl="threefry2x32")


def host_rng(seed: int) -> np.random.Generator:
    """The host's generator for ``seed`` (sampling which answers to check)."""
    return np.random.default_rng(_words(seed, 4))


@functools.lru_cache(maxsize=8)
def _generator(count: int, shape: tuple, dtype: str, sharding, dist: str):
    if dist not in DISTS:
        raise ValueError(f"input dist must be one of {DISTS}, got {dist!r}")
    dtype = jnp.dtype(dtype)
    real = jnp.finfo(dtype).dtype

    def one(key):
        if jnp.issubdtype(dtype, jnp.complexfloating):
            kr, ki = jax.random.split(key)
            return jax.lax.complex(jax.random.normal(kr, shape, real),
                                   jax.random.normal(ki, shape, real))
        return jax.random.normal(key, shape, dtype)

    def make(key):
        return tuple(one(jax.random.fold_in(key, i)) for i in range(count))

    out = None if sharding is None else (sharding,) * count
    return jax.jit(make, out_shardings=out)


def fields(seed: int, count: int, shape, dtype, sharding=None,
           spec: dict | None = None) -> tuple:
    """``count`` fields of ``shape``/``dtype`` from ``seed``, made on the
    device in one call, each placed with ``sharding``.  Field i is the same
    whatever ``count`` is."""
    dist = (spec or {}).get("dist", "normal")
    gen = _generator(int(count), tuple(shape), jnp.dtype(dtype).name,
                     sharding, dist)
    return gen(seed_key(seed))
