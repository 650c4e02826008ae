"""jit'd public wrappers around the Pallas kernels.

Complex arrays are split into float32 planes at this boundary; callers see
normal complex64 in/out.  ``interpret=None`` resolves per backend
(``kernels/backend.py``): Mosaic on TPU, the interpreter elsewhere.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import fft_matmul, spectral_scale


@functools.partial(jax.jit, static_argnames=("sign", "interpret"))
def fft_matmul_1d(x: jax.Array, sign: int = -1, interpret: bool | None = None):
    """Batched 1-D FFT along the last axis of a complex64 array (any rank)."""
    shape = x.shape
    n = shape[-1]
    b = 1
    for s in shape[:-1]:
        b *= s
    xr = jnp.real(x).astype(jnp.float32).reshape(b, n)
    xi = jnp.imag(x).astype(jnp.float32).reshape(b, n)
    yr, yi = fft_matmul.fft4step_planes(xr, xi, sign, interpret=interpret)
    return jax.lax.complex(yr, yi).reshape(shape)


@functools.partial(jax.jit, static_argnames=("alpha", "interpret"))
def spectral_scale_op(x: jax.Array, h: jax.Array, alpha: float = 1.0,
                      interpret: bool | None = None):
    """alpha * x * h with h of shape (N,) broadcast against x (..., N)."""
    shape = x.shape
    n = shape[-1]
    b = 1
    for s in shape[:-1]:
        b *= s
    xr = jnp.real(x).astype(jnp.float32).reshape(b, n)
    xi = jnp.imag(x).astype(jnp.float32).reshape(b, n)
    hr = jnp.real(h).astype(jnp.float32)
    hi = jnp.imag(h).astype(jnp.float32)
    yr, yi = spectral_scale.spectral_scale_planes(xr, xi, hr, hi, alpha,
                                                  interpret=interpret)
    return jax.lax.complex(yr, yi).reshape(shape)
