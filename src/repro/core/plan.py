"""FFT plans: precomputed DFT matrices and twiddle factors.

CROFT's "option 2/4 — single FFTW3 plan" amortizes plan creation across all
1-D transforms.  The XLA analogue of an FFTW plan is the set of *constants*
a transform needs — DFT matrices for the four-step (Bailey) factorization and
twiddle factors — plus the static factorization decision itself.  A cached
:class:`FFTPlan` makes these compile-time constants (planned once, reused for
every 1-D FFT in the 3-D transform); ``plan_cache=False`` reproduces CROFT's
"multiple plans" options 1/3 by re-materializing the constants with runtime
ops inside every call, so the extra work is visible in the lowered HLO
exactly like repeated ``fftw_plan_dft_1d`` calls are visible in an MPI trace.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

# Largest DFT applied as a single matmul.  In the schedule executor's
# stacked real/imag planes form (``FFTPlan.planes``) a 64-point stage is
# one real 128x128 contraction, one MXU tile on TPU; the complex einsums
# of ``local_fft.fft_matmul`` (the local paths) never use that form.
MAX_RADIX = 64


def _is_pow2(n: int) -> bool:
    return n >= 1 and (n & (n - 1)) == 0


def split_factors(n: int, max_radix: int = MAX_RADIX) -> tuple[int, int]:
    """Balanced n = n1 * n2 split with n1 <= max_radix, n1 >= n2 bias.

    Power-of-two sizes only (the paper's own restriction: N = 2^n).
    """
    if not _is_pow2(n):
        raise ValueError(f"CROFT requires power-of-two sizes, got {n}")
    if n <= max_radix:
        return n, 1
    p = int(math.log2(n))
    p1 = min(int(math.log2(max_radix)), (p + 1) // 2)
    # bias n1 up to max_radix so the matmul dimension stays MXU-sized
    p1 = min(int(math.log2(max_radix)), max(p1, p - int(math.log2(max_radix))))
    # ensure n2 = n / n1 also recursable
    return 2 ** p1, 2 ** (p - p1)


def dft_matrix(n: int, sign: int, dtype=np.complex64) -> np.ndarray:
    """Dense DFT matrix W[j, k] = exp(sign * 2πi * j * k / n)."""
    jk = np.outer(np.arange(n), np.arange(n))
    return np.exp(sign * 2j * np.pi * jk / n).astype(dtype)


def twiddle_matrix(n1: int, n2: int, sign: int, dtype=np.complex64) -> np.ndarray:
    """Four-step inter-stage twiddles T[n2, k1] = exp(sign*2πi*k1*n2/(n1*n2)).

    Laid out (n2, k1) to match ``local_fft.fft_matmul``'s post-stage-1
    operand layout.
    """
    k1 = np.arange(n1)
    j2 = np.arange(n2)
    return np.exp(sign * 2j * np.pi * np.outer(j2, k1) / (n1 * n2)).astype(dtype)


def planes_matrix(wr, wi, xp=np):
    """Real and imaginary parts of complex matrices (..., n, m) -> the
    stacked-real (..., 2, n, 2, m) operator ``W[c, j, d, k]`` that maps
    stacked planes ``x[c, j]`` (c = 0 real, 1 imaginary) to ``y[d, k]``:
    ``yr = xr @ wr - xi @ wi``, ``yi = xr @ wi + xi @ wr``.  ``xp`` is
    numpy (planned constants) or jax.numpy (rebuilt at run time)."""
    return xp.stack([xp.stack([wr, wi], -2), xp.stack([-wi, wr], -2)], -4)


def planes_angles(n1: int, n2: int, sign: int, xp=np, ftype=np.float64):
    """Phase angles of the planes four-step's stage matrices, ``2*pi*sign``
    times: stage 1 is the n1-point DFT with the twiddles of each j2
    folded in, ``A1[j2, j1, k1] = j1*k1/n1 + j2*k1/(n1*n2)`` (shape
    (n2, n1, n1)), stage 2 the n2-point DFT ``A2[j2, k2] = j2*k2/n2``.
    With ``n2 == 1`` stage 1 is the whole DFT, shape (n1, n1).  Integer
    products are reduced modulo the length before the division, so the
    phases stay exact in ``ftype``."""
    j1 = xp.arange(n1)
    a1 = (xp.outer(j1, j1) % n1).astype(ftype) / n1
    if n2 == 1:
        return 2 * np.pi * sign * a1, None
    j2 = xp.arange(n2)
    tw = xp.outer(j2, j1).astype(ftype) / (n1 * n2)          # [j2, k1]
    a2 = (xp.outer(j2, j2) % n2).astype(ftype) / n2
    return (2 * np.pi * sign * (a1[None] + tw[:, None, :]),
            2 * np.pi * sign * a2)


def stacked_real(w: np.ndarray) -> np.ndarray:
    """Complex (n, n) matrix -> stacked-real (2n, 2n) for one-dot complex matmul.

    [xr xi] @ [[Wr, Wi], [-Wi, Wr]] == [Re(x@W), Im(x@W)].
    """
    wr, wi = w.real.astype(np.float32), w.imag.astype(np.float32)
    top = np.concatenate([wr, wi], axis=1)
    bot = np.concatenate([-wi, wr], axis=1)
    return np.concatenate([top, bot], axis=0)


@dataclasses.dataclass(frozen=True)
class FFTPlan:
    """Plan for a 1-D FFT of power-of-two size ``n`` (four-step factorized).

    Holds numpy constants; they become XLA constants when closed over in a
    jitted function (the "planned" path) or are rebuilt with runtime ops when
    the plan cache is disabled.
    """

    n: int
    n1: int
    n2: int
    sign: int  # -1 forward, +1 inverse
    dtype: np.dtype
    w1: np.ndarray  # (n1, n1) complex DFT matrix
    w2: Optional[np.ndarray]  # (n2, n2) or None when n2 == 1
    tw: Optional[np.ndarray]  # (n2, n1) twiddles or None when n2 == 1

    def constants_jnp(self, rematerialize: bool = False):
        """Return (w1, w2, tw) as jnp complex arrays.

        With ``rematerialize=True`` ("multiple plans" mode, CROFT options
        1/3) the constants are recomputed with runtime jnp ops on every call
        instead of being baked in as literals.
        """
        if not rematerialize:
            return (jnp.asarray(self.w1),
                    None if self.w2 is None else jnp.asarray(self.w2),
                    None if self.tw is None else jnp.asarray(self.tw))
        # runtime re-planning: iota/outer/exp show up in the HLO per call
        sign = self.sign

        def _dft(n):
            j = jnp.arange(n, dtype=jnp.float32)
            ang = (sign * 2.0 * jnp.pi / n) * jnp.outer(j, j)
            return jax.lax.complex(jnp.cos(ang), jnp.sin(ang)).astype(self.dtype)

        w1 = _dft(self.n1)
        w2 = _dft(self.n2) if self.n2 > 1 else None
        if self.n2 > 1:
            k1 = jnp.arange(self.n1, dtype=jnp.float32)
            j2 = jnp.arange(self.n2, dtype=jnp.float32)
            ang = (sign * 2.0 * jnp.pi / self.n) * jnp.outer(j2, k1)
            tw = jax.lax.complex(jnp.cos(ang), jnp.sin(ang)).astype(self.dtype)
        else:
            tw = None
        return w1, w2, tw

    def _planes(self, xp, ftype):
        a1, a2 = planes_angles(self.n1, self.n2, self.sign, xp, ftype)
        p1 = planes_matrix(xp.cos(a1), xp.sin(a1), xp)
        if self.w2 is None:
            return p1, None
        return p1, planes_matrix(xp.cos(a2), xp.sin(a2), xp)

    @functools.cached_property
    def planes(self):
        """(p1, p2): the stacked-real stage matrices of the planes
        four-step (:func:`planes_matrix` of :func:`planes_angles`), built
        in float64 and rounded once to the plan's real dtype.  ``p1`` is
        (n2, 2, n1, 2, n1), the twiddles folded in, or (2, n, 2, n) when
        ``n2 == 1``; ``p2`` is (2, n2, 2, n2), or None with one stage or
        ``n2 > max_radix`` (``local_fft.fft_planes`` then recurses)."""
        rtype = np.finfo(self.dtype).dtype
        return tuple(None if p is None else p.astype(rtype)
                     for p in self._planes(np, np.float64))

    def planes_jnp(self, rematerialize: bool = False):
        """:attr:`planes` as jnp arrays; with ``rematerialize=True`` they
        are recomputed with runtime ops on every call (the "multiple
        plans" mode of :meth:`constants_jnp`)."""
        if rematerialize:
            return self._planes(jnp, np.finfo(self.dtype).dtype)
        return tuple(None if p is None else jnp.asarray(p)
                     for p in self.planes)


@functools.lru_cache(maxsize=256)
def make_plan(n: int, sign: int = -1, dtype_name: str = "complex64",
              max_radix: int = MAX_RADIX) -> FFTPlan:
    """The cached planner — CROFT's "single plan" path."""
    dtype = np.dtype(dtype_name)
    n1, n2 = split_factors(n, max_radix)
    w1 = dft_matrix(n1, sign, dtype)
    if n2 > 1:
        # w2 used only on the two-level path; recursion re-plans for n2>MAX
        w2_size = n2 if n2 <= max_radix else None
        w2 = dft_matrix(n2, sign, dtype) if w2_size else None
        tw = twiddle_matrix(n1, n2, sign, dtype)
    else:
        w2, tw = None, None
    return FFTPlan(
        n=n, n1=n1, n2=n2, sign=sign, dtype=dtype,
        w1=w1, w2=w2, tw=tw,
    )


def plan_cache_info():
    return make_plan.cache_info()


def clear_plan_cache():
    make_plan.cache_clear()
