"""Pallas kernel: fused complex pointwise multiply-scale in frequency space.

y = alpha * x * h  — the inner op of spectral solvers (Poisson multiplier,
convolution filters) and of the 3-D inverse normalization.  Fusing the
complex product with the scalar keeps the frequency-domain round trip at one
HBM read + one write per plane instead of four.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import backend
from repro.obs import scopes


def _scale_kernel(xr_ref, xi_ref, hr_ref, hi_ref, or_ref, oi_ref, *,
                  alpha: float):
    xr = xr_ref[...] * alpha
    xi = xi_ref[...] * alpha
    hr = hr_ref[...]
    hi = hi_ref[...]
    or_ref[...] = xr * hr - xi * hi
    oi_ref[...] = xr * hi + xi * hr


def spectral_scale_planes(xr, xi, hr, hi, alpha: float = 1.0, *,
                          block_rows: int = 0,
                          interpret: bool | None = None):
    """(B, N) f32 planes times (N,)-broadcast filter planes."""
    interpret = backend.resolve_interpret(interpret)
    b, n = xr.shape
    if block_rows <= 0:
        block_rows = backend.pick_block_rows(b, n, 6)
    grid = (b // block_rows,)
    hr2 = hr.reshape(1, n)
    hi2 = hi.reshape(1, n)
    kernel = functools.partial(_scale_kernel, alpha=alpha)
    return pl.pallas_call(
        kernel,
        name="croft_spectral_scale",
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, n), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, n), lambda i: (i, 0)),
            pl.BlockSpec((1, n), lambda i: (0, 0)),
            pl.BlockSpec((1, n), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_rows, n), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, n), lambda i: (i, 0)),
        ],
        out_shape=backend.f32_outputs((b, n), 2, xr, xi, hr, hi),
        interpret=interpret,
    )(xr, xi, hr2, hi2)


def spectral_scale_planes_full(xr, xi, hr, hi, alpha: float = 1.0, *,
                               block_rows: int = 0,
                               interpret: bool | None = None):
    """(B, N) f32 planes times same-shape (B, N) filter planes (the full
    3-D k-space filter of a spectral solver, flattened to rows)."""
    interpret = backend.resolve_interpret(interpret)
    b, n = xr.shape
    if block_rows <= 0:
        block_rows = backend.pick_block_rows(b, n, 6)
    grid = (b // block_rows,)
    kernel = functools.partial(_scale_kernel, alpha=alpha)
    blk = pl.BlockSpec((block_rows, n), lambda i: (i, 0))
    return pl.pallas_call(
        kernel,
        name="croft_spectral_scale_full",
        grid=grid,
        in_specs=[blk, blk, blk, blk],
        out_specs=[blk, blk],
        out_shape=backend.f32_outputs((b, n), 2, xr, xi, hr, hi),
        interpret=interpret,
    )(xr, xi, hr, hi)


@scopes.role(scopes.SCALE)
def spectral_scale(x: jax.Array, h: jax.Array, alpha: float = 1.0, *,
                   use_pallas: bool | None = None,
                   interpret: bool | None = None) -> jax.Array:
    """Fused ``alpha * x * h`` on complex arrays (the schedule-epilogue op).

    ``h`` must broadcast against ``x``.  On TPU (or ``use_pallas=True``)
    same-shape complex64 operands route through the Pallas plane kernel;
    everywhere else the plain jnp product is emitted — XLA fuses it into
    the surrounding jit, which is the point of attaching the multiply as
    a schedule epilogue instead of paying a second dispatch and an extra
    HBM round trip over the spectrum.
    """
    if use_pallas is None:
        use_pallas = backend.on_tpu()
    if use_pallas and x.dtype == jnp.complex64 and h.shape == x.shape:
        b, n = math.prod(x.shape[:-1]), x.shape[-1]
        xr = jnp.real(x).astype(jnp.float32).reshape(b, n)
        xi = jnp.imag(x).astype(jnp.float32).reshape(b, n)
        hr = jnp.real(h).astype(jnp.float32).reshape(b, n)
        hi = jnp.imag(h).astype(jnp.float32).reshape(b, n)
        yr, yi = spectral_scale_planes_full(xr, xi, hr, hi, alpha,
                                            interpret=interpret)
        return jax.lax.complex(yr, yi).reshape(x.shape)
    y = x * h
    if alpha != 1.0:
        y = y * jnp.asarray(alpha, y.dtype)
    return y


@scopes.role(scopes.SCALE)
def spectral_scale_stacked(p: jax.Array, h: jax.Array, alpha: float = 1.0, *,
                           use_pallas: bool | None = None,
                           interpret: bool | None = None) -> jax.Array:
    """:func:`spectral_scale` on stacked planes ``p`` (2, ...) (the
    schedule executor's form); ``h`` is complex and broadcasts against
    one plane.  Same-shape f32 operands take the Pallas plane kernel on
    TPU (or with ``use_pallas=True``)."""
    if use_pallas is None:
        use_pallas = backend.on_tpu()
    hr, hi = jnp.real(h), jnp.imag(h)
    if use_pallas and p.dtype == jnp.float32 and h.shape == p.shape[1:]:
        b, n = math.prod(p.shape[1:-1]), p.shape[-1]
        yr, yi = spectral_scale_planes_full(
            p[0].reshape(b, n), p[1].reshape(b, n), hr.reshape(b, n),
            hi.reshape(b, n), alpha, interpret=interpret)
        return jnp.stack([yr, yi]).reshape(p.shape)
    xr, xi = p[0], p[1]
    if alpha != 1.0:
        xr, xi = xr * alpha, xi * alpha
    return jnp.stack([xr * hr - xi * hi, xr * hi + xi * hr])
