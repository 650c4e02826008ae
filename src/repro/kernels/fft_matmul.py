"""Pallas TPU kernel: batched 1-D FFT via the four-step (Bailey) matmul
factorization — the MXU-native replacement for FFTW's butterfly kernels
(DESIGN.md §2, hardware adaptation).

Layout decisions:
  * complex data travels as separate float32 real/imag planes (TPU Pallas has
    no complex registers);
  * each DFT matmul is ONE real matmul against a stacked-real matrix
      [xr xi] @ [[Wr, Wi], [-Wi, Wr]] = [Re(xW), Im(xW)]
    run at ``Precision.HIGHEST`` (f32 contraction);
  * every in-kernel operation works on 2-D (rows, lanes) slabs cut at
    128-lane boundaries — Mosaic refuses the (rows, n1, n2) reshapes and
    transposes of a textbook four-step.

For N <= 128 the kernel is one dense DFT matmul.  For N = n1 * 128
(n1 <= 32) it splits j = 128*j1 + j2, k = k1 + n1*k2:

  stage 1  S_k1 = sum_j1 W_n1[j1, k1] * x[:, 128*j1 : 128*(j1+1)]
           (complex axpys of 128-lane slabs by constant scalars, VPU)
  stage 2  S_k1 *= T[k1, j2] = exp(sign*2πi*k1*j2/N)         (twiddles)
  stage 3  out[:, 128*k1 : 128*(k1+1)] = S_k1 @ W_128        (MXU)

so the kernel writes Y[k1 + n1*k2] at lane 128*k1 + k2; the wrapper's
one (B, n1, 128) -> (B, 128, n1) transpose restores natural order.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core import plan as plan_lib
from repro.kernels import backend

LANES = backend.LANES
#: largest stage-1 radix (N = MAX_N1 * 128): stage 1 costs n1^2 slab
#: axpys, so the kernel stops where that outgrows the MXU stage
MAX_N1 = 32
MAX_N = MAX_N1 * LANES

_HIGHEST = jax.lax.Precision.HIGHEST


def _snap(v: float) -> float:
    """Exact 0/±1 for the trivial roots of unity (skips their multiply)."""
    r = round(v)
    return float(r) if abs(v - r) < 1e-12 else float(v)


def _cmul_const(xr, xi, c: complex):
    a, b = _snap(c.real), _snap(c.imag)
    if b == 0.0:
        return (xr, xi) if a == 1.0 else (a * xr, a * xi)
    if a == 0.0:
        return (-b * xi, b * xr)
    return a * xr - b * xi, a * xi + b * xr


def _dense_kernel(xr_ref, xi_ref, w_ref, or_ref, oi_ref):
    """N <= 128: (Bb, 2N) @ (2N, 2N) stacked-real DFT."""
    n = xr_ref.shape[-1]
    xs = jnp.concatenate([xr_ref[...], xi_ref[...]], axis=1)
    ys = jnp.dot(xs, w_ref[...], precision=_HIGHEST,
                 preferred_element_type=jnp.float32)
    or_ref[...] = ys[:, :n]
    oi_ref[...] = ys[:, n:]


def _fft4step_kernel(xr_ref, xi_ref, w2_ref, twr_ref, twi_ref,
                     or_ref, oi_ref, *, w1: np.ndarray):
    """N = n1 * 128: stage-1 slab axpys, twiddles, stage-3 matmuls."""
    n1 = w1.shape[0]
    xr = [xr_ref[:, j * LANES:(j + 1) * LANES] for j in range(n1)]
    xi = [xi_ref[:, j * LANES:(j + 1) * LANES] for j in range(n1)]
    for k1 in range(n1):
        sr, si = _cmul_const(xr[0], xi[0], w1[0, k1])
        for j1 in range(1, n1):
            tr, ti = _cmul_const(xr[j1], xi[j1], w1[j1, k1])
            sr, si = sr + tr, si + ti
        twr = twr_ref[k1:k1 + 1, :]
        twi = twi_ref[k1:k1 + 1, :]
        zr, zi = sr * twr - si * twi, sr * twi + si * twr
        zs = jnp.concatenate([zr, zi], axis=1)               # (Bb, 256)
        ys = jnp.dot(zs, w2_ref[...], precision=_HIGHEST,
                     preferred_element_type=jnp.float32)
        or_ref[:, k1 * LANES:(k1 + 1) * LANES] = ys[:, :LANES]
        oi_ref[:, k1 * LANES:(k1 + 1) * LANES] = ys[:, LANES:]


def fft4step_planes(xr: jax.Array, xi: jax.Array, sign: int = -1, *,
                    block_rows: int = 0,
                    interpret: Optional[bool] = None) -> tuple:
    """Batched FFT over float32 planes of shape (B, N); N a power of two,
    N <= ``MAX_N``.  Returns (yr, yi) in natural frequency order.
    """
    interpret = backend.resolve_interpret(interpret)
    b, n = xr.shape
    if not plan_lib._is_pow2(n) or n > MAX_N:
        raise ValueError(
            f"the Pallas FFT kernel takes power-of-two N <= {MAX_N}, got "
            f"N={n}; use local_impl='matmul' for this length")
    row = lambda shape: pl.BlockSpec(shape, lambda i: (i, 0))
    const = lambda shape: pl.BlockSpec(shape, lambda i: (0, 0))
    out_shape = backend.f32_outputs((b, n), 2, xr, xi)

    if n <= LANES:
        if block_rows <= 0:
            block_rows = backend.pick_block_rows(b, n, 10)
        w = jnp.asarray(plan_lib.stacked_real(
            plan_lib.dft_matrix(n, sign, np.complex128)))
        return tuple(pl.pallas_call(
            _dense_kernel,
            name="croft_fft_dense",
            grid=(b // block_rows,),
            in_specs=[row((block_rows, n)), row((block_rows, n)),
                      const(w.shape)],
            out_specs=[row((block_rows, n))] * 2,
            out_shape=out_shape,
            interpret=interpret,
        )(xr, xi, w))

    n1 = n // LANES
    if block_rows <= 0:
        block_rows = backend.pick_block_rows(b, n, 12)
    w1 = plan_lib.dft_matrix(n1, sign, np.complex128)
    w2 = jnp.asarray(plan_lib.stacked_real(
        plan_lib.dft_matrix(LANES, sign, np.complex128)))
    tw = np.exp(sign * 2j * np.pi
                * np.outer(np.arange(n1), np.arange(LANES)) / n)
    twr = jnp.asarray(tw.real, jnp.float32)                   # (n1, 128)
    twi = jnp.asarray(tw.imag, jnp.float32)
    yr, yi = pl.pallas_call(
        functools.partial(_fft4step_kernel, w1=w1),
        name="croft_fft4step",
        grid=(b // block_rows,),
        in_specs=[row((block_rows, n)), row((block_rows, n)),
                  const(w2.shape), const(twr.shape), const(twi.shape)],
        out_specs=[row((block_rows, n))] * 2,
        out_shape=out_shape,
        interpret=interpret,
    )(xr, xi, w2, twr, twi)
    # kernel lane 128*k1 + k2 holds Y[k1 + n1*k2]
    def unscramble(y):
        return y.reshape(b, n1, LANES).swapaxes(1, 2).reshape(b, n)
    return unscramble(yr), unscramble(yi)
