"""Pseudo-spectral DNS of incompressible flow in a periodic box: one RK4
substage as one program on the chip, over a ``Croft3D`` r2c plan.

The method is that of Mortensen & Langtangen, "High performance Python
for direct numerical simulations of turbulent flows", Comput. Phys.
Commun. 203 (2016) 53-65 (the spectralDNS code): the Navier–Stokes
equations in rotational form on a (2 pi)^3 box,

    dU/dt = P(k) [M(k) F(u x omega)] - nu |k|^2 U,

with U the velocity's r2c half spectrum, omega = curl u, M the 2/3-rule
dealiasing mask, P(k) = I - k k^T / |k|^2 the Leray projection (it
takes the pressure's place), and classical RK4 with a = (1/6, 1/3, 1/3,
1/6) and b = (1/2, 1/2, 1).  One substage (:meth:`NavierStokes.substage`):

1. omega_hat = i k x U, in plain jnp;
2. six c2r transforms, u and omega, in two calls of the plan's inverse
   on three stacked fields each.  Each stage of a transform holds its
   input and output planes whole, 3.2 GB each for six fields at 512^3:
   compiled for a v5e, one six-field call took 12.8 GiB of temporaries
   besides the 4.5 GiB state, more than the chip's 16 GiB, and two
   three-field calls take 7.5 GiB;
3. u x omega in physical space, in plain jnp;
4. three r2c transforms of the product, one call of the plan's forward;
5-8. mask, projection, viscous term and the RK4 update of (U, U0, U1)
   in one Pallas kernel (``kernels/ns_update.py``).

Departures from the source: the substage index rides as an argument of
one compiled program, so U0 (the step's start) and U1 (the step's
accumulated update) are set from U inside substage 0 rather than before
the loop of substages; each substage transforms the velocity itself,
where the source transforms it for substage 0 at the end of the step
before (the same six c2r a substage); float32 throughout, where the
source runs float64.

The transforms are callables over a stack of fields (``forward``: real
(B, Nx, Ny, Nz) to (B, Nx, Ny, Nz//2 + 1) complex; ``inverse`` back, with
1/N), so the plan's own packed r2c entries, which take leading batch
axes, run here as they run for one field.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ns_update
from repro.obs import scopes
from repro.obs.tracer import get_tracer

#: classical RK4, as Mortensen & Langtangen (2016) integrate
RK4_A = (1.0 / 6.0, 1.0 / 3.0, 1.0 / 3.0, 1.0 / 6.0)
RK4_B = (0.5, 0.5, 1.0)


def wavenumbers(shape) -> tuple:
    """(kx, ky, kz) float32, shaped to broadcast over the half spectrum:
    kx and ky in FFT order, kz in rfft order."""
    nx, ny, nz = shape
    kx = np.fft.fftfreq(nx, 1.0 / nx).astype(np.float32)
    ky = np.fft.fftfreq(ny, 1.0 / ny).astype(np.float32)
    kz = np.arange(nz // 2 + 1, dtype=np.float32)
    return kx[:, None, None], ky[None, :, None], kz[None, None, :]


def curl(u_hat, k) -> jax.Array:
    """i k x U of a (3, ...) spectrum stack."""
    kx, ky, kz = k
    ux, uy, uz = u_hat[0], u_hat[1], u_hat[2]
    return 1j * jnp.stack([ky * uz - kz * uy, kz * ux - kx * uz,
                           kx * uy - ky * ux])


def cross(a, b) -> jax.Array:
    """a x b of two (3, ...) stacks."""
    return jnp.stack([a[1] * b[2] - a[2] * b[1], a[2] * b[0] - a[0] * b[2],
                      a[0] * b[1] - a[1] * b[0]])


def rk4_coefficients(rk) -> jax.Array:
    """(a, b, first, last) of substage ``rk`` (0..3), f32; b is 0 at the
    last substage, which takes U from U1."""
    table = jnp.asarray([[RK4_A[s], RK4_B[s] if s < 3 else 0.0,
                          float(s == 0), float(s == 3)] for s in range(4)],
                        jnp.float32)
    return table[rk]


def _planes(z) -> jax.Array:
    """(3, Nx, Ny, Nh) complex -> the kernel's (2, 3, Nh, Nx, Ny) planes."""
    return jnp.stack([jnp.real(z), jnp.imag(z)]).transpose(0, 1, 4, 2, 3)


def _complex(p) -> jax.Array:
    """Inverse of :func:`_planes`."""
    return jax.lax.complex(p[0], p[1]).transpose(0, 2, 3, 1)


class NavierStokes:
    """RK4 substages of a pseudo-spectral DNS over the transforms of an r2c
    plan of grid ``shape``.

    >>> plan = Croft3D((512,) * 3, None, problem="r2c", strategy="packed")
    >>> ns = NavierStokes(plan.forward, plan.inverse, plan.shape,
    ...                   nu=1e-3, dt=1e-3)
    >>> state = ns.start(u_hat)          # (3, Nx, Ny, Nz//2 + 1) complex64
    >>> for i in range(4 * steps):
    ...     state = ns.substage(state, i % 4)

    :meth:`substage` donates the state it is given.
    """

    def __init__(self, forward, inverse, shape, *, nu: float, dt: float):
        self.shape = tuple(shape)
        self.nu, self.dt = float(nu), float(dt)
        self._forward, self._inverse = forward, inverse

        # a named function: its XLA module name tells it apart in a trace
        def croft_ns_substage(state, rk):
            scale = jnp.asarray([self.dt, self.dt, 1.0, 1.0], jnp.float32)
            return self._program(state, rk4_coefficients(rk) * scale)

        self._substage = jax.jit(croft_ns_substage, donate_argnums=0)

    def start(self, u_hat: jax.Array) -> tuple:
        """The state (U, U0, U1) of a run from the velocity spectrum
        ``u_hat``; U0 and U1 are set at the next substage 0."""
        return (u_hat, jnp.zeros_like(u_hat), jnp.zeros_like(u_hat))

    def substage(self, state: tuple, rk) -> tuple:
        """RK4 substage ``rk`` (0..3) of ``state`` = (U, U0, U1), donated;
        the new state."""
        with get_tracer().span("croft.ns_substage"):
            return self._substage(state, rk)

    def lower(self, sharding=None):
        """:meth:`substage`'s program, lowered at this grid's shapes."""
        nx, ny, nz = self.shape
        spec = jax.ShapeDtypeStruct((3, nx, ny, nz // 2 + 1), jnp.complex64,
                                    sharding=sharding)
        rk = jax.ShapeDtypeStruct((), jnp.int32, sharding=sharding)
        return self._substage.lower((spec, spec, spec), rk)

    def _program(self, state, coef):
        u_hat, u0_hat, u1_hat = state
        k = wavenumbers(self.shape)
        with scopes.stage("ns-curl"), jax.named_scope(scopes.SCALE):
            w_hat = curl(u_hat, k)
        u, w = self._inverse(u_hat), self._inverse(w_hat)   # (3, Nx, Ny, Nz)
        with scopes.stage("ns-cross"), jax.named_scope(scopes.SCALE):
            product = cross(u, w)
        n_hat = self._forward(product)                # (3, Nx, Ny, Nh)
        with scopes.stage("ns-update"):
            with jax.named_scope(scopes.RELAYOUT):
                planes = [_planes(z) for z in (n_hat, u_hat, u0_hat, u1_hat)]
            with jax.named_scope(scopes.SCALE):
                out = ns_update.ns_update_planes(
                    coef, *planes, shape=self.shape, nu=self.nu)
            with jax.named_scope(scopes.RELAYOUT):
                return tuple(_complex(p) for p in out)

