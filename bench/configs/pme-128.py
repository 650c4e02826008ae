"""pme-128: the reciprocal-space mesh of smooth particle-mesh Ewald (SPME).

Plain formulas of Essmann et al., J. Chem. Phys. 103, 8577 (1995), in
float64 numpy, with GROMACS's way of choosing the Ewald splitting (its
``calc_ewaldcoeff_q``: erfc(beta * rc) = ewald-rtol).

The potential on the grid is  phi = IFFT[ FFT(Q) * G ]  with the
influence function

    G(m) = f * K^3 * B(m) * exp(-pi^2 |m|^2 / beta^2) / (pi V |m|^2),
    G(0) = 0,

where m runs over the reciprocal vectors m_i / L of the cubic box (m_i in
FFT order), B(m) = |b1(m1)|^2 |b2(m2)|^2 |b3(m3)|^2 are the cardinal
B-spline moduli of order n, f = 1/(4 pi eps0) in kJ mol^-1 nm e^-2, and
K^3 undoes the 1/K^3 that the inverse DFT carries (Essmann eq. 4.7 with
theta_rec = F[B C]).  Then phi is in kJ mol^-1 e^-1 at the grid points for
charges Q in e.
"""

from __future__ import annotations

import math

import numpy as np

#: 1/(4 pi eps0) in kJ mol^-1 nm e^-2 (GROMACS ONE_4PI_EPS0)
ONE_4PI_EPS0 = 138.935458


def ewald_beta(rtol: float, rc: float) -> float:
    """beta (nm^-1) with erfc(beta * rc) = rtol, by bisection."""
    lo, hi = 0.0, 5.0
    while math.erfc(hi * rc) > rtol:
        hi *= 2
    for _ in range(200):
        mid = (lo + hi) / 2
        if math.erfc(mid * rc) > rtol:
            lo = mid
        else:
            hi = mid
    return (lo + hi) / 2


def bspline_values(order: int) -> np.ndarray:
    """M_n(k) for k = 0 .. n (the cardinal B-spline of order n at the
    integers), by the recursion M_n(x) = x/(n-1) M_{n-1}(x)
    + (n-x)/(n-1) M_{n-1}(x-1) from M_2(x) = 1 - |x - 1|."""
    def m(n, x):
        if n == 2:
            return max(0.0, 1.0 - abs(x - 1.0))
        return (x * m(n - 1, x) + (n - x) * m(n - 1, x - 1.0)) / (n - 1)
    return np.array([m(order, float(k)) for k in range(order + 1)])


def bspline_moduli(k: int, order: int) -> np.ndarray:
    """|b(m)|^2 for m = 0 .. K-1 (Essmann eq. 4.4)."""
    mn = bspline_values(order)
    m = np.arange(k)[:, None]
    j = np.arange(order - 1)[None, :]
    denom = np.sum(mn[1:order][None, :] * np.exp(2j * np.pi * m * j / k),
                   axis=1)
    return 1.0 / np.abs(denom) ** 2


def kspace_filter(cfg: dict) -> np.ndarray:
    """G on the r2c half spectrum, shape (K, K, K//2 + 1), float64."""
    p = cfg["spme"]
    kx, ky, kz = cfg["shape"]
    if not kx == ky == kz:
        raise ValueError("the SPME influence function here is for a cubic "
                         "grid in a cubic box")
    k = kx
    box = float(p["box_nm"])
    beta = ewald_beta(float(p["ewald_rtol"]), float(p["rcoulomb_nm"]))
    order = int(p["pme_order"])
    full = np.fft.fftfreq(k, 1.0 / k)           # integers in FFT order
    half = np.arange(k // 2 + 1, dtype=np.float64)
    b = bspline_moduli(k, order)
    bx, bz = b, b[: k // 2 + 1]
    m2 = ((full[:, None, None] ** 2 + full[None, :, None] ** 2
           + half[None, None, :] ** 2) / box ** 2)
    volume = box ** 3
    with np.errstate(divide="ignore", invalid="ignore"):
        c = np.exp(-(math.pi ** 2) * m2 / beta ** 2) / (math.pi * volume * m2)
    c[0, 0, 0] = 0.0
    bmod = bx[:, None, None] * bx[None, :, None] * bz[None, None, :]
    return ONE_4PI_EPS0 * k ** 3 * bmod * c


def least_hbm_bytes(cfg: dict, traffic: dict) -> int:
    """Least HBM bytes of one step on its one chip: each transform reads
    its input once and writes its output once, and the filtered forward
    also reads the filter (complex64, as the program takes it)."""
    if traffic["step"] != "filtered_inverse":
        raise ValueError(f"no byte count for step kind {traffic['step']!r}")
    kx, ky, kz = cfg["shape"]
    real = kx * ky * kz * 4                      # float32 grid
    spectrum = kx * ky * (kz // 2 + 1) * 8       # complex64 half spectrum
    forward_filtered = real + spectrum + spectrum   # grid, filter -> spec.
    inverse = spectrum + real
    return forward_filtered + inverse
