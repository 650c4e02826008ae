"""Pallas kernel: the k-space end of a pseudo-spectral Navier–Stokes RK4
substage, in one pass over the half spectra.

Per mode k of the r2c half spectrum (kx and ky in FFT order, kz in rfft
order, integer wavenumbers of a 2 pi box), given the transformed
nonlinear term N = rfft(u x omega) and the state (U, U0, U1):

    dU  = P(k) [M(k) N] - nu |k|^2 U       M: the 2/3-rule mask,
                                           P = I - k k^T / |k|^2 (Leray)
    U0' = U  if first else U0              (first: substage 0 of a step)
    U1' = U  if first else U1
    U1  = U1' + a dt dU
    U   = U1  if last else U0' + b dt dU   (last: substage 3)

The RK4 coefficients of the substage, ``(a dt, b dt, first, last)``,
ride as scalar-prefetch operands, so one compiled kernel serves all four
substages.  k, |k|^2 and the mask are made in the kernel from the grid
indices: no k array is read.  Each complex stack is passed as f32
planes (2, 3, Nh, Nx, Ny): real and imaginary parts on axis 0, the three
components on axis 1, which the projection needs together, then kz, x
and y.  So y lies on the lanes and x on the sublanes, and the half
spectrum's odd Nh = Nz/2 + 1 is a grid axis: a row-major (..., Nh) plane
would pad Nh to whole 128-lane tiles (257 to 384 at Nz = 512).  The
grid runs over kz and blocks of x rows.  The new state is written over
the planes of the old (``input_output_aliases``).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import backend

#: f32 planes of one grid step's windows: 4 inputs and 3 outputs, each
#: (real, imaginary) x 3 components
WINDOW_PLANES = 7 * 2 * 3


def dealias_kmax(shape) -> tuple:
    """The 2/3 rule's bound per axis, as Mortensen & Langtangen (2016)
    set it: a mode is kept where ``|k_i| < (2/3) (N_i // 2 + 1)`` on
    every axis."""
    return tuple(2.0 / 3.0 * (n // 2 + 1) for n in shape)


def freq(index, n: int):
    """FFT-order wavenumber of ``index`` on an axis of ``n`` points (as
    ``numpy.fft.fftfreq(n, 1 / n)``)."""
    return jnp.where(index < (n + 1) // 2, index, index - n)


def dealias(kx, ky, kz, kmax) -> jax.Array:
    """Where the 2/3 rule keeps the mode (bool)."""
    return ((jnp.abs(kx) < kmax[0]) & (jnp.abs(ky) < kmax[1])
            & (kz < kmax[2]))


def leray(n, kx, ky, kz, inv_k2):
    """The divergence-free part of ``n`` = (n_x, n_y, n_z), one plane of
    each: n - k (k . n) / |k|^2; the k = 0 mode passes (``inv_k2`` 0)."""
    kdn = (kx * n[0] + ky * n[1] + kz * n[2]) * inv_k2
    return [n[0] - kx * kdn, n[1] - ky * kdn, n[2] - kz * kdn]


def _update_kernel(coef_ref, n_ref, u_ref, u0_ref, u1_ref,
                   uo_ref, u0o_ref, u1o_ref, *, shape, nu: float):
    nx, ny, _ = shape
    rows, cols = n_ref.shape[-2:]
    iz, i = pl.program_id(0), pl.program_id(1)
    ix = i * rows + jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 0)
    iy = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1)
    kx = freq(ix, nx).astype(jnp.float32)
    ky = freq(iy, ny).astype(jnp.float32)
    kz = jnp.zeros((rows, cols), jnp.float32) + iz.astype(jnp.float32)
    k2 = kx * kx + ky * ky + kz * kz
    inv_k2 = jnp.where(k2 > 0, 1.0 / jnp.where(k2 > 0, k2, 1.0), 0.0)
    keep = dealias(kx, ky, kz, dealias_kmax(shape))
    a_dt, b_dt = coef_ref[0], coef_ref[1]
    first, last = coef_ref[2] > 0.5, coef_ref[3] > 0.5
    for p in range(2):                      # real, then imaginary planes
        masked = [jnp.where(keep, n_ref[p, c], 0.0) for c in range(3)]
        proj = leray(masked, kx, ky, kz, inv_k2)
        for c in range(3):
            u = u_ref[p, c]
            du = proj[c] - nu * k2 * u
            u0 = jnp.where(first, u, u0_ref[p, c])
            u1 = jnp.where(first, u, u1_ref[p, c]) + a_dt * du
            u0o_ref[p, c] = u0
            u1o_ref[p, c] = u1
            uo_ref[p, c] = jnp.where(last, u1, u0 + b_dt * du)


def ns_update_planes(coef, n, u, u0, u1, *, shape, nu: float,
                     block_rows: int = 0,
                     interpret: Optional[bool] = None):
    """The substage's k-space update on f32 planes.

    ``coef``: (4,) f32, ``(a dt, b dt, first, last)`` (``first`` and
    ``last`` 1.0 or 0.0).  ``n``, ``u``, ``u0``, ``u1``: (2, 3, Nh, Nx,
    Ny) planes of N, U, U0 and U1 for the grid ``shape`` = (Nx, Ny, Nz),
    Nh = Nz // 2 + 1.  Returns the planes of the new (U, U0, U1), in the
    buffers of ``u``, ``u0`` and ``u1``."""
    interpret = backend.resolve_interpret(interpret)
    nx, ny, nz = shape
    planes = (2, 3, nz // 2 + 1, nx, ny)
    if any(a.shape != planes for a in (n, u, u0, u1)):
        raise ValueError(f"planes of shape {planes} expected, got "
                         f"{[a.shape for a in (n, u, u0, u1)]}")
    if block_rows <= 0:
        block_rows = backend.pick_block_rows(nx, ny, WINDOW_PLANES)
    blk = pl.BlockSpec((2, 3, pl.Squeezed(), block_rows, ny),
                       lambda iz, i, c: (0, 0, iz, i, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(planes[2], nx // block_rows),
        in_specs=[blk] * 4, out_specs=[blk] * 3)
    kernel = functools.partial(_update_kernel, shape=tuple(shape),
                               nu=float(nu))
    return pl.pallas_call(
        kernel,
        name="croft_ns_update",
        grid_spec=grid_spec,
        out_shape=backend.f32_outputs(planes, 3, n, u, u0, u1),
        input_output_aliases={2: 0, 3: 1, 4: 2},
        interpret=interpret,
    )(coef, n, u, u0, u1)
