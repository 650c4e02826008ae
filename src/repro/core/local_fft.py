"""Local (single-device) 1-D FFT building blocks.

CROFT calls FFTW's 1-D routine along each axis; on TPU the idiomatic
equivalent is the four-step (Bailey) factorization applied as MXU matmuls
(see DESIGN.md §2).  Three interchangeable implementations:

- ``fft_matmul``   four-step via einsum (lowers everywhere; what the
                   distributed transform uses by default, and the oracle the
                   Pallas kernel is checked against)
- ``fft_stockham`` radix-2 decimation-in-time, vectorized (VPU-style)
- ``fft_xla``      ``jnp.fft.fft`` (XLA's FFT HLO; reference)

All operate along the *last* axis; callers move axes.  Forward sign=-1,
inverse sign=+1 unnormalized (normalization applied at the 3-D level, eq. (2)
of the paper).

The transforms themselves (:func:`fft3d_local`, the packed real paths
of ``repro.real`` and the schedule executor, ``core/schedule.run_schedule``)
carry their block as stacked real/imaginary planes instead
(:func:`to_planes`) and run each axis with :func:`fft_along`: for
``matmul`` the planes four-step :func:`fft_planes`, one real contraction
per stage along the axis where it lies; the other implementations
convert at the op.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import plan as plan_lib
from repro.obs import scopes


@scopes.role(scopes.DFT)
def fft_xla(x: jax.Array, sign: int = -1) -> jax.Array:
    return jnp.fft.fft(x) if sign == -1 else jnp.fft.ifft(x) * x.shape[-1]


@scopes.role(scopes.DFT)
def _apply_dft_matrix(x: jax.Array, w: jax.Array) -> jax.Array:
    # x (..., n), w (n, k): complex matmul on the MXU (XLA decomposes to
    # real dots); contraction over the last axis.
    return jnp.einsum("...n,nk->...k", x, w, precision=jax.lax.Precision.HIGHEST)


def fft_matmul(x: jax.Array, sign: int = -1, *, plan_cache: bool = True,
               max_radix: int = plan_lib.MAX_RADIX) -> jax.Array:
    """Four-step FFT along the last axis.  Supports any power-of-two size.

    n <= max_radix           : single DFT matmul
    n <= max_radix**2        : reshape (n1, n2); DFT(n1) matmul; twiddle;
                               DFT(n2) matmul; transpose
    larger                   : six-step recursion on the n2 axis
    """
    n = x.shape[-1]
    plan = plan_lib.make_plan(n, sign, str(x.dtype), max_radix)
    with jax.named_scope(scopes.DFT):
        w1, w2, tw = plan.constants_jnp(rematerialize=not plan_cache)
    if plan.n2 == 1:
        return _apply_dft_matrix(x, w1)

    batch = x.shape[:-1]
    n1, n2 = plan.n1, plan.n2
    # n = n2*j1 + j2  (row-major reshape)
    with jax.named_scope(scopes.RELAYOUT):
        xr = x.reshape(batch + (n1, n2))
    with jax.named_scope(scopes.DFT):
        # stage 1: DFT over j1 -> (..., n2, k1)
        y = jnp.einsum("...jt,jk->...tk", xr, w1,
                       precision=jax.lax.Precision.HIGHEST)
        # stage 2: twiddles T[j2, k1]
        y = y * tw
    if n2 <= max_radix:
        # stage 3: DFT over j2 -> (..., k1, k2): contract the t axis
        with jax.named_scope(scopes.DFT):
            z = jnp.einsum("...tk,ts->...ks", y, w2,
                           precision=jax.lax.Precision.HIGHEST)
    else:
        # six-step: recurse along the n2 axis (currently axis -2); move it
        # last, recurse, move back
        with jax.named_scope(scopes.RELAYOUT):
            y = jnp.swapaxes(y, -1, -2)  # (..., k1, n2)
        z = fft_matmul(y, sign, plan_cache=plan_cache, max_radix=max_radix)
        # z[..., k1, k2] already
    # output index k = k1 + n1*k2  -> lay out (..., k2, k1) then ravel
    with jax.named_scope(scopes.RELAYOUT):
        z = jnp.swapaxes(z, -1, -2)
        return z.reshape(batch + (n,))


@scopes.role(scopes.RELAYOUT)
def to_planes(x: jax.Array) -> jax.Array:
    """Complex (...) -> real planes (2, ...): ``[Re x, Im x]`` stacked on
    a new leading axis.  A real array gets a plane axis of size 1."""
    if jnp.iscomplexobj(x):
        return jnp.stack([jnp.real(x), jnp.imag(x)])
    return x[None]


@scopes.role(scopes.RELAYOUT)
def from_planes(p: jax.Array) -> jax.Array:
    """Inverse of :func:`to_planes`."""
    if p.shape[0] == 1:
        return p[0]
    return jax.lax.complex(p[0], p[1])


@scopes.role(scopes.DFT)
def _contract(spec: str, p: jax.Array, w: jax.Array,
              batch: str = "") -> jax.Array:
    """``einsum(spec)`` as one dot_general in its natural output order
    (batch, lhs free, rhs free: W's output dims minor), then the
    transpose to ``spec``'s order, which the TPU compiler folds into the
    dot's layout.  ``batch`` names leading dims of ``p`` (after the
    planes) that become dot batch dims, W broadcast over them.  So the
    dot's rows are one field's pencils, W's columns stay minor, and a
    K-chunk or a batch of fields changes only the row count or the batch
    count: the CPU backend, which picks its GEMM by size, then rounds
    each chunk and each field as it rounds the whole or the one field."""
    ins, out = spec.split("->")
    lhs, rhs = ins.split(",")
    if batch:
        w = jnp.broadcast_to(w, p.shape[1:1 + len(batch)] + w.shape)
        rhs = batch + rhs
    lead = [a for a in rhs if a in lhs and a in out]
    nat = "".join(lead + [a for a in lhs if a in out and a not in lead]
                  + [a for a in rhs if a in out and a not in lead])
    y = jnp.einsum(f"{lhs},{rhs}->{nat}", p, w,
                   precision=jax.lax.Precision.HIGHEST)
    return jnp.transpose(y, [nat.index(a) for a in out])


def fft_planes(p: jax.Array, axis: int, sign: int = -1, *, nbatch: int = 0,
               plan_cache: bool = True) -> jax.Array:
    """Four-step FFT along ``axis`` of stacked planes ``p`` (2, ...).

    Each stage is one real contraction of (plane, input index) against a
    stacked-real matrix (``FFTPlan.planes``), on the axis where it lies:
    no axis moves.  With ``n = n1 * n2`` (``plan.split_factors``) the
    axis is viewed as (j1, j2), ``j = n2*j1 + j2``:

    stage 1  contract (plane, j1) with the n1-point DFT, the twiddles of
             each j2 folded into its matrix: a dot batched over j2
    stage 2  contract (plane, j2) with the n2-point DFT, output laid out
             (k2, k1), so ``k = k1 + n1*k2`` is natural order

    ``n <= plan.MAX_RADIX`` is one contraction; ``n2 > MAX_RADIX``
    recurses along j2.  The ``nbatch`` dims after the planes are independent
    fields (leading batch axes): dot batch dims.  A real block (one
    plane) contracts with stage 1's real rows only.
    """
    n = p.shape[axis]
    cname = "complex128" if p.dtype == jnp.float64 else "complex64"
    plan = plan_lib.make_plan(n, sign, cname)
    with jax.named_scope(scopes.DFT):
        w1, w2 = plan.planes_jnp(rematerialize=not plan_cache)
        if p.shape[0] == 1:  # a real block: no imaginary plane to contract
            w1 = w1[..., :1, :, :, :]
    lead = "ABCDEFGHIJKLMNOPQRSTUVWXYZ"[:p.ndim - 1]  # dims past the planes
    pre, post, bat = lead[:axis - 1], lead[axis:], lead[:nbatch]
    if plan.n2 == 1:
        return _contract(f"c{pre}j{post},cjdk->d{pre}k{post}", p, w1, bat)
    n1, n2 = plan.n1, plan.n2
    with jax.named_scope(scopes.RELAYOUT):
        p = p.reshape(p.shape[:axis] + (n1, n2) + p.shape[axis + 1:])
    # stage 1: (plane, j1) -> (plane, k1), batched over j2
    y = _contract(f"c{pre}jt{post},tcjdk->d{pre}kt{post}", p, w1, bat)
    if w2 is not None:
        # stage 2: (plane, j2) -> (plane, k2), laid out (k2, k1)
        z = _contract(f"c{pre}kt{post},ctds->d{pre}sk{post}", y, w2, bat)
    else:
        z = fft_planes(y, axis + 1, sign, nbatch=nbatch,
                       plan_cache=plan_cache)
        with jax.named_scope(scopes.RELAYOUT):
            z = jnp.swapaxes(z, axis, axis + 1)
    with jax.named_scope(scopes.RELAYOUT):
        return z.reshape(z.shape[:axis] + (n,) + z.shape[axis + 2:])


@scopes.role(scopes.DFT)
def fft_stockham(x: jax.Array, sign: int = -1, *, plan_cache: bool = True) -> jax.Array:
    """Radix-2 DIT FFT along the last axis (power-of-two sizes).

    Vectorized butterflies; the per-stage twiddles are plan constants.  This
    is the "CPU-shaped" algorithm kept for contrast with the matmul path.
    """
    n = x.shape[-1]
    if not plan_lib._is_pow2(n):
        raise ValueError(f"power-of-two sizes only, got {n}")
    stages = int(math.log2(n))
    # bit-reversal permutation as a static gather
    idx = np.arange(n)
    rev = np.zeros(n, dtype=np.int32)
    for b in range(stages):
        rev |= ((idx >> b) & 1) << (stages - 1 - b)
    y = x[..., rev]
    for s in range(stages):
        m = 1 << (s + 1)  # butterfly span
        half = m // 2
        if plan_cache:
            tw_np = np.exp(sign * 2j * np.pi * np.arange(half) / m).astype(
                np.dtype(str(x.dtype)))
            tw = jnp.asarray(tw_np)
        else:
            k = jnp.arange(half, dtype=jnp.float32)
            ang = (sign * 2.0 * jnp.pi / m) * k
            tw = jax.lax.complex(jnp.cos(ang), jnp.sin(ang)).astype(x.dtype)
        yr = y.reshape(y.shape[:-1] + (n // m, m))
        even, odd = yr[..., :half], yr[..., half:]
        t = odd * tw
        y = jnp.concatenate([even + t, even - t], axis=-1).reshape(y.shape)
    return y


_IMPLS = {"matmul": fft_matmul, "stockham": fft_stockham, "xla": fft_xla}


def fft_1d(x: jax.Array, axis: int, sign: int = -1, *, impl: str = "matmul",
           plan_cache: bool = True) -> jax.Array:
    """1-D FFT along ``axis`` of a complex block with the chosen
    implementation (moved to the last axis and back)."""
    if impl == "pallas":
        from repro.kernels import ops as kernel_ops  # lazy: optional dep path
        fn = scopes.role(scopes.DFT)(
            lambda v: kernel_ops.fft_matmul_1d(v, sign=sign))
    elif impl == "xla":
        fn = lambda v: fft_xla(v, sign)
    else:
        base = _IMPLS[impl]
        fn = lambda v: base(v, sign, plan_cache=plan_cache)
    with jax.named_scope(scopes.RELAYOUT):
        x = jnp.moveaxis(x, axis, -1)
    y = fn(x)
    with jax.named_scope(scopes.RELAYOUT):
        return jnp.moveaxis(y, -1, axis)


def fft_along(p: jax.Array, axis: int, sign: int = -1, *,
              impl: str = "matmul", nbatch: int = 0,
              plan_cache: bool = True) -> jax.Array:
    """1-D FFT along ``axis`` of planes ``p`` with ``nbatch`` leading
    batch axes: the planes four-step for ``matmul``; the other
    implementations convert at the op."""
    if impl == "matmul":
        return fft_planes(p, axis, sign, nbatch=nbatch, plan_cache=plan_cache)
    y = fft_1d(from_planes(p), axis - 1, sign, impl=impl,
               plan_cache=plan_cache)
    return to_planes(y)


def fft3d_local(x: jax.Array, sign: int = -1, *, impl="matmul",
                plan_cache: bool = True, norm: Optional[str] = None) -> jax.Array:
    """Single-device 3-D FFT over the last three axes (x, y, z order).

    Runs on planes from entry to exit; leading axes are independent
    fields (dot batch dims).  ``impl`` may be a 3-tuple of
    implementations, one per axis in transform order (x, y, z) — the
    per-stage form of ``FFTOptions.local_impl``.
    """
    assert x.ndim >= 3
    with scopes.stage("x-fft"):
        p = to_planes(x)
    for stage in range(3):
        stage_impl = impl[stage] if isinstance(impl, (tuple, list)) else impl
        with scopes.stage("xyz"[stage] + "-fft"):
            p = fft_along(p, p.ndim - 3 + stage, sign, impl=stage_impl,
                          nbatch=p.ndim - 4, plan_cache=plan_cache)
    p = apply_norm(p, sign, norm)
    with scopes.stage("z-fft"):
        return from_planes(p)


@scopes.role(scopes.SCALE)
def apply_norm(x: jax.Array, sign: int, norm: Optional[str]) -> jax.Array:
    """Paper convention (eq. 2): forward unnormalized, inverse 1/(NxNyNz);
    ``x`` may be a block or its planes (the last three axes are x, y, z)."""
    nxyz = x.shape[-3] * x.shape[-2] * x.shape[-1]
    if norm is None or norm == "backward":
        return x / nxyz if sign == +1 else x
    if norm == "ortho":
        return x / jnp.sqrt(jnp.asarray(nxyz, x.dtype))
    if norm == "none":
        return x
    raise ValueError(f"unknown norm {norm!r}")
