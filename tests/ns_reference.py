"""The plain reference of ``repro.solvers.navier_stokes``: the right-hand
side and RK4 substage of Mortensen & Langtangen (2016), as their
spectralDNS code writes them, in float64 numpy with ``numpy.fft``.
Nothing here comes from the program under test.

    dU = P(k) [M(k) rfftn(u x omega)] - nu |k|^2 U,   u = irfftn(U),
                                                      omega = irfftn(i k x U)

M keeps a mode where |k_i| < (2/3) (N_i // 2 + 1) on every axis; P = I -
k k^T / |k|^2, with the k = 0 mode left as it is.
"""

import numpy as np

A = (1 / 6, 1 / 3, 1 / 3, 1 / 6)
B = (1 / 2, 1 / 2, 1.0)
AXES = (1, 2, 3)


def wavenumbers(shape):
    nx, ny, nz = shape
    kx = np.fft.fftfreq(nx, 1 / nx)[:, None, None]
    ky = np.fft.fftfreq(ny, 1 / ny)[None, :, None]
    kz = np.fft.rfftfreq(nz, 1 / nz)[None, None, :]
    return np.broadcast_arrays(kx, ky, kz)


def dealias(shape):
    k = wavenumbers(shape)
    keep = np.ones(k[0].shape, bool)
    for ki, n in zip(k, shape):
        keep &= np.abs(ki) < 2 / 3 * (n // 2 + 1)
    return keep


def cross(a, b):
    return np.stack([a[1] * b[2] - a[2] * b[1], a[2] * b[0] - a[0] * b[2],
                     a[0] * b[1] - a[1] * b[0]])


def rhs(u_hat, nu, shape, mask=True, project=True):
    """dU/dt of the (3, Nx, Ny, Nz//2 + 1) spectrum ``u_hat``."""
    u_hat = np.asarray(u_hat, np.complex128)
    k = np.stack(wavenumbers(shape))
    k2 = np.sum(k * k, axis=0)
    u = np.fft.irfftn(u_hat, s=shape, axes=AXES)
    omega = np.fft.irfftn(1j * cross(k, u_hat), s=shape, axes=AXES)
    n_hat = np.fft.rfftn(cross(u, omega), axes=AXES)
    if mask:
        n_hat = n_hat * dealias(shape)
    if project:
        n_hat = n_hat - k * (np.sum(k * n_hat, axis=0)
                             / np.where(k2 == 0, 1, k2))
    return n_hat - nu * k2 * u_hat


def substage(state, rk, nu, dt, shape):
    """(U, U0, U1) after RK4 substage ``rk``, as the program orders it."""
    u, u0, u1 = (np.asarray(s, np.complex128) for s in state)
    if rk == 0:
        u0, u1 = u, u
    du = rhs(u, nu, shape)
    u1 = u1 + A[rk] * dt * du
    return (u1 if rk == 3 else u0 + B[rk] * dt * du), u0, u1


def rk4_step(u_hat, nu, dt, shape):
    """U after one whole RK4 step."""
    state = (u_hat, u_hat, u_hat)
    for rk in range(4):
        state = substage(state, rk, nu, dt, shape)
    return state[0]


def divergence(u_hat, shape):
    """max |k . U| over max |k| |U|."""
    k = np.stack(wavenumbers(shape))
    u_hat = np.asarray(u_hat, np.complex128)
    kdu = np.abs(np.sum(k * u_hat, axis=0)).max()
    scale = (np.sqrt(np.sum(k * k, axis=0))
             * np.sqrt(np.sum(np.abs(u_hat) ** 2, axis=0))).max()
    return kdu / scale


def solenoidal_field(shape, seed, slope=-5 / 3):
    """A seeded random solenoidal spectrum with E(k) ~ |k|^slope over
    every mode the 2/3 rule keeps (none elsewhere, none at k = 0), and
    u_rms = 1: white noise in physical space, shaped in k space."""
    rng = np.random.default_rng(seed)
    u_hat = np.fft.rfftn(rng.standard_normal((3,) + tuple(shape)),
                         axes=AXES)
    k = np.stack(wavenumbers(shape))
    k2 = np.sum(k * k, axis=0)
    kmag = np.sqrt(np.where(k2 == 0, 1, k2))
    amp = np.where(dealias(shape) & (k2 > 0), kmag ** ((slope - 2) / 2), 0)
    u_hat = u_hat * amp
    u_hat = u_hat - k * np.sum(k * u_hat, axis=0) / np.where(k2 == 0, 1, k2)
    u = np.fft.irfftn(u_hat, s=shape, axes=AXES)
    return u_hat / np.sqrt(np.mean(u * u))
