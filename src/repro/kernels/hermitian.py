"""Pallas kernels for the real-transform hot steps (repro.real).

Two fused plane kernels in the style of ``spectral_scale.py`` (f32
real/imag planes, row-blocked grid, compiled on TPU and interpreted
elsewhere, see ``kernels/backend.py``):

unpack_two_for_one_planes   C = FFT(a + i*b) of two packed real pencils
                            -> the two half spectra A, B via Hermitian
                            symmetry, with the (real) Nyquist bin folded
                            into the (real) DC bin's imaginary slot —
                            one HBM read of C, one write of A and B,
                            instead of the 6+ passes the unfused
                            flip/conj/axpy chain costs.

hermitian_extend_planes     the exact inverse: folded half spectra A, B
                            -> the full length-n packed spectrum
                            C[k] = A[k] + i*B[k], C[n-k] = conj(A[k] - i*B[k]),
                            ready for one complex inverse FFT.

Rows are independent z-lines (the caller flattens (..., pairs) into the
row axis); each block sees full rows, so the frequency reversal
k -> (-k) mod n stays inside the block, where :func:`negate_lanes` does
it with in-vreg lane gathers.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import backend

LANES = backend.LANES


def negate_lanes(x: jax.Array) -> jax.Array:
    """In-kernel frequency negation along lanes: out[:, k] = x[:, (-k) mod w].

    Mosaic has no lane reversal (``jnp.flip`` lowers to ``rev``), but it
    gathers within one 128-lane vreg.  So each 128-lane output tile
    gathers the mirrored input tile with lane index (-k) mod 128, and
    lane 0 of each tile, which maps across the tile boundary, comes from
    lane 0 of the next input tile.  Exact: a permutation, no arithmetic.
    """
    w = x.shape[-1]
    t = min(w, LANES)
    if w % t:
        raise ValueError(f"lane negation needs width <= {LANES} or a "
                         f"multiple of it, got {w}")
    m = w // t
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape[:-1] + (t,), x.ndim - 1)
    idx = (t - lane) % t
    tiles = [x[..., i * t:(i + 1) * t] for i in range(m)]
    out = []
    for q in range(m):
        body = jnp.take_along_axis(tiles[m - 1 - q], idx, axis=-1)
        out.append(jnp.where(lane == 0, tiles[(m - q) % m][..., :1], body))
    return out[0] if m == 1 else jnp.concatenate(out, axis=-1)


def _unpack_kernel(cr_ref, ci_ref, ar_ref, ai_ref, br_ref, bi_ref):
    n = cr_ref.shape[-1]
    nz2 = n // 2
    cr = cr_ref[...]
    ci = ci_ref[...]
    lo_r, lo_i = cr[:, :nz2], ci[:, :nz2]
    # w[k] = C[(-k) mod n] for k = 1..nz2-1, and w[0] = C[nz2] (Nyquist)
    w_r, w_i = negate_lanes(cr[:, nz2:]), negate_lanes(ci[:, nz2:])
    # A = (C + conj(C[-k])) / 2, B = (C - conj(C[-k])) / 2i; bin 0 folds
    # (DC, Nyquist), both real for a real transform, into one complex bin
    lane0 = jax.lax.broadcasted_iota(jnp.int32, lo_r.shape, 1) == 0
    ar_ref[...] = jnp.where(lane0, lo_r, 0.5 * (lo_r + w_r))
    ai_ref[...] = jnp.where(lane0, w_r, 0.5 * (lo_i - w_i))
    br_ref[...] = jnp.where(lane0, lo_i, 0.5 * (lo_i + w_i))
    bi_ref[...] = jnp.where(lane0, w_i, -0.5 * (lo_r - w_r))


def unpack_two_for_one_planes(cr, ci, *, block_rows: int = 0,
                              interpret: Optional[bool] = None):
    """(B, n) f32 planes of C -> four (B, n//2) planes (Ar, Ai, Br, Bi)."""
    interpret = backend.resolve_interpret(interpret)
    b, n = cr.shape
    if n % 2:
        raise ValueError(f"two-for-one fold needs even n, got {n}")
    if block_rows <= 0:
        block_rows = backend.pick_block_rows(b, n, 6)
    nz2 = n // 2
    grid = (b // block_rows,)
    in_spec = pl.BlockSpec((block_rows, n), lambda i: (i, 0))
    out_spec = pl.BlockSpec((block_rows, nz2), lambda i: (i, 0))
    return pl.pallas_call(
        _unpack_kernel,
        name="croft_hermitian_unpack",
        grid=grid,
        in_specs=[in_spec, in_spec],
        out_specs=[out_spec] * 4,
        out_shape=backend.f32_outputs((b, nz2), 4, cr, ci),
        interpret=interpret,
    )(cr, ci)


def _extend_kernel(sar_ref, sai_ref, sbr_ref, sbi_ref, cr_ref, ci_ref):
    sar = sar_ref[...]
    sai = sai_ref[...]
    sbr = sbr_ref[...]
    sbi = sbi_ref[...]
    lane0 = jax.lax.broadcasted_iota(jnp.int32, sar.shape, 1) == 0
    # bins 0..nz2-1: C[0] = A[0] + i B[0] (DC, real parts of bin 0);
    # C[k] = A[k] + i B[k]
    lo_r = jnp.where(lane0, sar, sar - sbi)
    lo_i = jnp.where(lane0, sbr, sai + sbr)
    # t[k] = conj(A[k] - i B[k]) = C[n-k] for k >= 1; t[0] = C[nz2], the
    # Nyquist bin folded into bin 0's imaginary slots.  Bins nz2..n-1
    # are t[(-m) mod nz2] for m = 0..nz2-1.
    t_r = jnp.where(lane0, sai, sar + sbi)
    t_i = jnp.where(lane0, sbi, sbr - sai)
    cr_ref[...] = jnp.concatenate([lo_r, negate_lanes(t_r)], -1)
    ci_ref[...] = jnp.concatenate([lo_i, negate_lanes(t_i)], -1)


def hermitian_extend_planes(sar, sai, sbr, sbi, *, block_rows: int = 0,
                            interpret: Optional[bool] = None):
    """Four (B, nz2) folded half-spectrum planes -> (B, 2*nz2) C planes."""
    interpret = backend.resolve_interpret(interpret)
    b, nz2 = sar.shape
    n = 2 * nz2
    if block_rows <= 0:
        block_rows = backend.pick_block_rows(b, n, 6)
    grid = (b // block_rows,)
    in_spec = pl.BlockSpec((block_rows, nz2), lambda i: (i, 0))
    out_spec = pl.BlockSpec((block_rows, n), lambda i: (i, 0))
    return pl.pallas_call(
        _extend_kernel,
        name="croft_hermitian_extend",
        grid=grid,
        in_specs=[in_spec] * 4,
        out_specs=[out_spec] * 2,
        out_shape=backend.f32_outputs((b, n), 2, sar, sai, sbr, sbi),
        interpret=interpret,
    )(sar, sai, sbr, sbi)
