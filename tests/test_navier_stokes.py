"""The pseudo-spectral Navier–Stokes substage (``repro.solvers``) against
the float64 numpy reference (``tests/ns_reference.py``) at 32^3 on the
CPU, the update kernel in interpret mode.

Tolerances, each with its reason (readings at 32^3 on three seeds):

- ``RHS_TOL`` on dU, over max |dU|: the float32 DFTs of the plan round
  at about 3e-7 of the largest term; bfloat16 DFTs read 3e-3 and more,
  the mask left out 0.2, the projection left out 1.3.
- ``STEP_TOL`` on U after one RK4 step, over the step's widest change:
  float32 U rounds at about 1e-7 of |U|, which is 1.7e-4 of the change
  at dt = 1e-3; bfloat16 DFTs read 2.4e-3 and more.
- ``DIV_TOL`` on max |k . U| over max |k| |U| after the step: about
  4e-8 in float32; the projection left out reads 4e-3.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import ns_reference as ref
from repro.core import Croft3D
from repro.kernels import ns_update
from repro.solvers import navier_stokes

N = 32
SHAPE = (N, N, N)
NU, DT = 1e-3, 1e-3
RHS_TOL = 1e-5
STEP_TOL = 5e-4
DIV_TOL = 1e-6


def _bf16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _bf16_transforms():
    """r2c and c2r over a stack with bfloat16 inputs and outputs."""
    def cplx(z):
        return jax.lax.complex(_bf16(jnp.real(z)), _bf16(jnp.imag(z)))
    return (lambda v: cplx(jnp.fft.rfftn(_bf16(v), axes=(1, 2, 3))),
            lambda v: _bf16(jnp.fft.irfftn(cplx(v), s=SHAPE, axes=(1, 2, 3))))


def _solver(transforms=None):
    if transforms is None:
        plan = Croft3D(SHAPE, None, problem="r2c", strategy="packed")
        transforms = (plan.forward, plan.inverse)
    return navier_stokes.NavierStokes(*transforms, SHAPE, nu=NU, dt=DT)


def _rhs(solver, u_hat):
    """dU at ``u_hat``: substage 1 from zero U0 and U1 returns b dt dU as
    its U (b = 1/2), to float32's relative precision."""
    u = jnp.asarray(u_hat, jnp.complex64)
    state = (u, jnp.zeros_like(u), jnp.zeros_like(u))
    return np.asarray(solver.substage(state, 1)[0]) / (ref.B[1] * DT)


def _errors(solver, seed):
    """(dU error, RK4 step error, divergence after the step)."""
    u_hat = ref.solenoidal_field(SHAPE, seed)
    want = ref.rhs(u_hat, NU, SHAPE)
    got = _rhs(solver, u_hat)
    rhs_err = np.abs(got - want).max() / np.abs(want).max()
    state = solver.start(jnp.asarray(u_hat, jnp.complex64))
    for rk in range(4):
        state = solver.substage(state, rk)
    stepped = np.asarray(state[0])
    want_step = ref.rk4_step(u_hat, NU, DT, SHAPE)
    step_err = (np.abs(stepped - want_step).max()
                / np.abs(want_step - u_hat).max())
    return rhs_err, step_err, ref.divergence(stepped, SHAPE)


@pytest.mark.parametrize("seed", [0, 1])
def test_substage_matches_reference(seed):
    rhs_err, step_err, div = _errors(_solver(), seed)
    assert rhs_err < RHS_TOL
    assert step_err < STEP_TOL
    assert div < DIV_TOL


def test_initial_field_is_what_it_says():
    u_hat = ref.solenoidal_field(SHAPE, 3)
    u = np.fft.irfftn(u_hat, s=SHAPE, axes=(1, 2, 3))
    assert np.sqrt(np.mean(u * u)) == pytest.approx(1.0)
    assert ref.divergence(u_hat, SHAPE) < 1e-12
    kept = ref.dealias(SHAPE)
    k2 = sum(k * k for k in ref.wavenumbers(SHAPE))
    energy = np.sum(np.abs(u_hat) ** 2, axis=0)
    assert np.all(energy[kept & (k2 > 0)] > 0)
    assert np.all(u_hat[:, ~kept] == 0)


def _mask_off(monkeypatch):
    monkeypatch.setattr(ns_update, "dealias",
                        lambda kx, ky, kz, kmax: kx == kx)


def _projection_off(monkeypatch):
    monkeypatch.setattr(ns_update, "leray", lambda n, kx, ky, kz, inv: n)


@pytest.mark.parametrize("fault", ["mask off", "projection off",
                                   "bfloat16 DFTs"])
def test_faults_fail_the_tolerances(monkeypatch, fault):
    transforms = None
    if fault == "mask off":
        _mask_off(monkeypatch)
    elif fault == "projection off":
        _projection_off(monkeypatch)
    else:
        transforms = _bf16_transforms()
    rhs_err, step_err, div = _errors(_solver(transforms), 0)
    assert rhs_err > RHS_TOL and step_err > STEP_TOL
    if fault == "projection off":
        assert div > DIV_TOL


def test_rk4_coefficients():
    table = np.asarray([navier_stokes.rk4_coefficients(rk)
                        for rk in range(4)])
    np.testing.assert_allclose(table[:, 0], ref.A)
    np.testing.assert_allclose(table[:3, 1], ref.B)
    assert table[:, 2].tolist() == [1, 0, 0, 0]
    assert table[:, 3].tolist() == [0, 0, 0, 1]


def test_wavenumbers_and_mask_match_reference():
    k = navier_stokes.wavenumbers(SHAPE)
    for got, want in zip(k, ref.wavenumbers(SHAPE)):
        np.testing.assert_array_equal(np.broadcast_to(got, want.shape), want)
    full = [jnp.asarray(np.broadcast_to(ki, ref.dealias(SHAPE).shape))
            for ki in k]
    keep = np.asarray(ns_update.dealias(*full,
                                        ns_update.dealias_kmax(SHAPE)))
    np.testing.assert_array_equal(keep, ref.dealias(SHAPE))
    # 2/3 (N/2 + 1) = 11.33 at N = 32: |k| up to 11 is kept
    assert keep[11, 0, 0] and not keep[12, 0, 0] and keep[N - 11, 0, 0]


def test_update_kernel_blocks_of_rows_agree():
    """Eight x rows a block, as at 512^3, against the one block that 32^3
    takes by default: k from the block index must not change the update."""
    keys = jax.random.split(jax.random.key(5), 4)
    shape = (2, 3, N // 2 + 1, N, N)
    planes = [jax.random.normal(k, shape, jnp.float32) for k in keys]
    coef = jnp.asarray([0.3, 0.5, 0.0, 0.0], jnp.float32)
    one = ns_update.ns_update_planes(coef, *planes, shape=SHAPE, nu=NU)
    eight = ns_update.ns_update_planes(coef, *planes, shape=SHAPE, nu=NU,
                                       block_rows=8)
    for a, b in zip(one, eight):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_update_kernel_checks_plane_shapes():
    planes = jnp.zeros((2, 3, N // 2 + 1, N, N), jnp.float32)
    wrong = jnp.zeros((2, 3, N, N, N // 2 + 1), jnp.float32)
    coef = jnp.zeros(4, jnp.float32)
    with pytest.raises(ValueError, match="planes of shape"):
        ns_update.ns_update_planes(coef, wrong, planes, planes, planes,
                                   shape=SHAPE, nu=NU)


def test_substage_donates_and_keeps_shapes():
    solver = _solver()
    u_hat = jnp.asarray(ref.solenoidal_field(SHAPE, 4), jnp.complex64)
    state = solver.start(u_hat)
    new = solver.substage(state, 0)
    assert all(s.is_deleted() for s in state)
    assert [a.shape for a in new] == [(3, N, N, N // 2 + 1)] * 3
    assert all(a.dtype == jnp.complex64 for a in new)
