"""dns-512: one RK4 substage of a pseudo-spectral DNS of incompressible
flow (Mortensen & Langtangen, Comput. Phys. Commun. 203 (2016) 53-65).

Here, in plain ``jax.numpy`` float32 over the dense DFTs of
``bench/reference.py`` and nothing of the program under test: the
initial field, the right-hand side the check compares with, the
divergence, and the least bytes of a substage and of its update kernel.

On a (2 pi)^3 box with integer wavenumbers k (kx, ky in FFT order, kz in
rfft order), the velocity's r2c half spectrum U evolves as

    dU/dt = P(k) [M(k) rfft(u x omega)] - nu |k|^2 U,
    u = irfft(U),  omega = irfft(i k x U),

with M the 2/3 rule (a mode is kept where |k_i| < (2/3) (N_i // 2 + 1)
on every axis) and P = I - k k^T / |k|^2 (the k = 0 mode left as it is).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference, traffic


def wavenumbers(shape) -> tuple:
    """(kx, ky, kz) float32, shaped to broadcast over the half spectrum."""
    nx, ny, nz = shape
    return (jnp.asarray(np.fft.fftfreq(nx, 1 / nx), jnp.float32)[:, None, None],
            jnp.asarray(np.fft.fftfreq(ny, 1 / ny), jnp.float32)[None, :, None],
            jnp.arange(nz // 2 + 1, dtype=jnp.float32)[None, None, :])


def _kept(shape, k) -> jax.Array:
    keep = True
    for ki, n in zip(k, shape):
        keep = keep & (jnp.abs(ki) < 2 / 3 * (n // 2 + 1))
    return keep


def _project(v, k):
    k2 = sum(ki * ki for ki in k)
    kdv = sum(ki * vi for ki, vi in zip(k, v)) / jnp.where(k2 == 0, 1, k2)
    return [vi - ki * kdv for ki, vi in zip(k, v)]


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _shape_spectrum(noise_hat, shape, slope: float, u_rms: float):
    """White noise's spectrum -> solenoidal, E(k) ~ |k|^slope over the kept
    modes, u_rms as asked (Parseval over the half spectrum)."""
    k = wavenumbers(shape)
    k2 = sum(ki * ki for ki in k)
    amp = jnp.where(_kept(shape, k) & (k2 > 0),
                    jnp.where(k2 > 0, k2, 1) ** ((slope - 2) / 4), 0)
    u = jnp.stack(_project([noise_hat[c] * amp for c in range(3)], k))
    nz = shape[2]
    weight = jnp.where((k[2] == 0) | (k[2] == nz / 2), 1.0, 2.0)
    mean_sq = jnp.sum(weight * jnp.abs(u) ** 2) / (3 * float(np.prod(shape)) ** 2)
    return (u * (u_rms / jnp.sqrt(mean_sq))).astype(jnp.complex64)


def initial_field(cfg: dict, seed: int, spec: dict | None = None):
    """The velocity spectrum (3, Nx, Ny, Nz//2 + 1) complex64 that a run
    starts from: three fields of white noise from ``seed``
    (``bench/traffic.py``), each transformed by the reference, shaped to
    E(k) ~ k^slope over every mode the 2/3 rule keeps, made solenoidal
    and scaled to the configuration's u_rms.  Each phase is waited for
    before the next, so that the arrays it drops are freed and the
    device's peak memory does not depend on how far the host ran ahead."""
    shape = tuple(cfg["shape"])
    ns = cfg["ns"]
    rfft = reference.jitted("rfft3", "highest")
    noise = traffic.fields(seed, 3, shape, "float32", None, spec)
    spectra = jax.block_until_ready([rfft(x) for x in noise])
    del noise
    noise_hat = jax.block_until_ready(jnp.stack(spectra))
    del spectra
    return jax.block_until_ready(_shape_spectrum(
        noise_hat, shape, float(ns["spectrum_slope"]), float(ns["u_rms"])))


@functools.partial(jax.jit, static_argnums=(1, 2))
def _curl_component(u_hat, c: int, shape):
    k = wavenumbers(shape)
    a, b = (c + 1) % 3, (c + 2) % 3
    return 1j * (k[a] * u_hat[b] - k[b] * u_hat[a])


@functools.partial(jax.jit, static_argnums=(2,))
def _cross_component(u, w, c: int):
    a, b = (c + 1) % 3, (c + 2) % 3
    return u[a] * w[b] - u[b] * w[a]


@functools.partial(jax.jit, static_argnums=(2, 3))
def _finish(n_hat, u_hat, shape, nu: float):
    k = wavenumbers(shape)
    n = [jnp.where(_kept(shape, k), n_hat[c], 0) for c in range(3)]
    k2 = sum(ki * ki for ki in k)
    return jnp.stack([p - nu * k2 * u_hat[c]
                      for c, p in enumerate(_project(n, k))])


def reference_rhs(cfg: dict, u_hat, precision: str = "highest"):
    """dU/dt of the spectrum ``u_hat`` (3, Nx, Ny, Nz//2 + 1), one field
    at a time through the reference's dense DFTs at ``precision``."""
    shape = tuple(cfg["shape"])
    irfft = reference.jitted("irfft3", precision, nz=shape[2])
    rfft = reference.jitted("rfft3", precision)
    u = jnp.stack([irfft(u_hat[c]) for c in range(3)])
    w = jnp.stack([irfft(_curl_component(u_hat, c, shape)) for c in range(3)])
    n_hat = jnp.stack([rfft(_cross_component(u, w, c)) for c in range(3)])
    del u, w
    return _finish(n_hat, u_hat, shape, float(cfg["ns"]["nu"]))


@functools.partial(jax.jit, static_argnums=(1,))
def _divergence(u_hat, shape):
    k = wavenumbers(shape)
    kdu = jnp.abs(sum(ki * u_hat[c] for c, ki in enumerate(k)))
    mag = (jnp.sqrt(sum(ki * ki for ki in k))
           * jnp.sqrt(sum(jnp.abs(u_hat[c]) ** 2 for c in range(3))))
    return jnp.max(kdu), jnp.max(mag)


def divergence(cfg: dict, u_hat) -> float:
    """max |k . U| over max |k| |U|: 0 for a solenoidal field."""
    kdu, mag = (float(v) for v in _divergence(u_hat, tuple(cfg["shape"])))
    return kdu / mag if mag > 0 else float("inf")


def _sizes(cfg: dict) -> tuple:
    nx, ny, nz = cfg["shape"]
    real = nx * ny * nz * 4                       # float32 field
    half = nx * ny * (nz // 2 + 1) * 8            # complex64 half spectrum
    return real, half


def ns_update_bytes(cfg: dict) -> int:
    """Least HBM bytes of the update kernel in one substage: it reads the
    transformed product, U, U0 and U1 and writes U and U1, three
    components each (U0 changes only in substage 0)."""
    _, half = _sizes(cfg)
    return 18 * half


def least_hbm_bytes(cfg: dict, traffic_cfg: dict) -> int:
    """Least HBM bytes of one substage on its one chip: each transform
    reads its input once and writes its output once (6 c2r, 3 r2c), the
    cross product reads u and omega and writes their product, and the
    update kernel moves :func:`ns_update_bytes`."""
    if traffic_cfg["step"] != "ns_rk_stage":
        raise ValueError(f"no byte count for step kind "
                         f"{traffic_cfg['step']!r}")
    real, half = _sizes(cfg)
    inverse = 6 * (half + real)
    forward = 3 * (real + half)
    cross = 6 * real + 3 * real
    return inverse + forward + cross + ns_update_bytes(cfg)
