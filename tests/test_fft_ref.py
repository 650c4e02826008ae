"""Local FFT implementations vs numpy and the naive O(N^2) DFT."""

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import local_fft as lf
from repro.core import plan as plan_lib
from repro.kernels.ref import ref_fft_1d_naive


@pytest.mark.parametrize("n", [2, 8, 64, 128, 512, 4096, 16384])
@pytest.mark.parametrize("impl", ["matmul", "stockham"])
def test_fft_1d_matches_numpy(n, impl, rng):
    x = (rng.randn(3, n) + 1j * rng.randn(3, n)).astype(np.complex64)
    fn = lf.fft_matmul if impl == "matmul" else lf.fft_stockham
    y = np.asarray(fn(jnp.asarray(x)))
    ref = np.fft.fft(x, axis=-1)
    np.testing.assert_allclose(y, ref, rtol=0, atol=2e-4 * np.abs(ref).max())


@pytest.mark.parametrize("n", [8, 32])
def test_fft_matches_naive_dft(n, rng):
    """Independent of any library FFT."""
    x = (rng.randn(2, n) + 1j * rng.randn(2, n)).astype(np.complex64)
    y = np.asarray(lf.fft_matmul(jnp.asarray(x)))
    ref = ref_fft_1d_naive(x)
    np.testing.assert_allclose(y, ref, atol=1e-3)


@pytest.mark.parametrize("n", [64, 1024])
def test_inverse_roundtrip(n, rng):
    x = (rng.randn(2, n) + 1j * rng.randn(2, n)).astype(np.complex64)
    y = lf.fft_matmul(jnp.asarray(x), -1)
    xb = np.asarray(lf.fft_matmul(y, +1)) / n
    np.testing.assert_allclose(xb, x, atol=1e-4)


def test_plan_cache_and_rematerialized_agree(rng):
    x = (rng.randn(2, 256) + 1j * rng.randn(2, 256)).astype(np.complex64)
    a = np.asarray(lf.fft_matmul(jnp.asarray(x), plan_cache=True))
    b = np.asarray(lf.fft_matmul(jnp.asarray(x), plan_cache=False))
    np.testing.assert_allclose(a, b, atol=2e-3)


def test_plan_factorization():
    for n in [2, 64, 128, 4096, 1 << 16, 1 << 19]:
        p = plan_lib.make_plan(n)
        assert p.n1 * p.n2 == n
        assert p.n1 <= plan_lib.MAX_RADIX
    with pytest.raises(ValueError):
        plan_lib.split_factors(100)  # not a power of two


def test_fft3d_local(rng):
    x = (rng.randn(8, 16, 32) + 1j * rng.randn(8, 16, 32)).astype(np.complex64)
    y = np.asarray(lf.fft3d_local(jnp.asarray(x)))
    ref = np.fft.fftn(x)
    np.testing.assert_allclose(y, ref, atol=2e-4 * np.abs(ref).max())
    # paper eq. (2): backward(forward(x)) == x with 1/(NxNyNz)
    xb = np.asarray(lf.fft3d_local(jnp.asarray(y), sign=+1, norm="backward"))
    np.testing.assert_allclose(xb, x, atol=2e-4 * np.abs(x).max())


@pytest.mark.parametrize("sign", [-1, +1])
@pytest.mark.parametrize("impl", [
    ("matmul", "stockham", "xla"), ("xla", "matmul", "stockham"),
    ("stockham", "xla", "matmul"), "matmul", "xla"])
def test_fft3d_local_mixed_impls_match_numpy(impl, sign):
    """Per-stage impl tuples: ``matmul`` stages run on the planes, the
    others convert at the op; a leading batch axis rides along."""
    rng = np.random.default_rng(7)
    shape = (2, 8, 16, 32)
    x = (rng.standard_normal(shape)
         + 1j * rng.standard_normal(shape)).astype(np.complex64)
    y = np.asarray(lf.fft3d_local(jnp.asarray(x), sign, impl=impl,
                                  norm="none"))
    x64 = x.astype(np.complex128)
    axes = (1, 2, 3)
    ref = (np.fft.fftn(x64, axes=axes) if sign == -1
           else np.fft.ifftn(x64, axes=axes) * np.prod(shape[1:]))
    np.testing.assert_allclose(y, ref, rtol=0, atol=2e-6 * np.abs(ref).max())


def test_fft3d_local_of_a_real_block(rng):
    """A real block enters as one plane: stage 1 contracts its real rows
    only."""
    x = rng.randn(8, 16, 128).astype(np.float32)
    y = np.asarray(lf.fft3d_local(jnp.asarray(x)))
    ref = np.fft.fftn(x.astype(np.float64))
    np.testing.assert_allclose(y, ref, rtol=0, atol=2e-6 * np.abs(ref).max())


def test_rfft3d_local(rng):
    from repro.core.rfft import rfft3d, irfft3d
    x = rng.randn(8, 4, 16).astype(np.float32)
    y = np.asarray(rfft3d(jnp.asarray(x)))
    ref = np.fft.rfftn(x)
    np.testing.assert_allclose(y, ref, atol=2e-4 * np.abs(ref).max())
    xb = np.asarray(irfft3d(jnp.asarray(y), 16))
    np.testing.assert_allclose(xb, x, atol=2e-4)


# --- the schedule executor's planes four-step (local_fft.fft_planes) --------

@pytest.mark.parametrize("plan_cache", [True, False])
@pytest.mark.parametrize("sign", [-1, +1])
@pytest.mark.parametrize("n", [8, 64, 128, 512, 1024])
@pytest.mark.parametrize("batch", [False, True])
@pytest.mark.parametrize("axis", [0, 1, 2])
def test_fft_planes_matches_numpy(axis, batch, n, sign, plan_cache):
    """One stage (8, 64), the splits 128 = 16 x 8 and 512 = 32 x 16 and the
    square 1024 = 32 x 32, along each axis of a 3-D block, with and
    without a leading batch axis."""
    shape = [4, 3, 5]
    shape[axis] = n
    shape = ([2] if batch else []) + shape
    ax = axis + (1 if batch else 0)
    rng = np.random.default_rng(n + axis)
    x = (rng.standard_normal(shape)
         + 1j * rng.standard_normal(shape)).astype(np.complex64)
    p = lf.fft_planes(lf.to_planes(jnp.asarray(x)), ax + 1, sign,
                      plan_cache=plan_cache)
    y = np.asarray(lf.from_planes(p))
    x64 = x.astype(np.complex128)
    ref = (np.fft.fft(x64, axis=ax) if sign == -1
           else np.fft.ifft(x64, axis=ax) * n)
    np.testing.assert_allclose(y, ref, rtol=0,
                               atol=2e-6 * np.abs(ref).max())


def test_fft_planes_recurses_past_max_radix_squared(rng):
    n = 8192  # 64 x 128: stage 2 is itself a four-step
    assert plan_lib.make_plan(n).planes[1] is None
    x = (rng.randn(2, n) + 1j * rng.randn(2, n)).astype(np.complex64)
    y = np.asarray(lf.from_planes(lf.fft_planes(
        lf.to_planes(jnp.asarray(x)), 2)))
    ref = np.fft.fft(x.astype(np.complex128), axis=-1)
    np.testing.assert_allclose(y, ref, rtol=0, atol=2e-6 * np.abs(ref).max())


def test_planes_constants_planned_or_rebuilt():
    """Planned: float32 literals rounded once from float64, the twiddles
    folded into stage 1.  ``plan_cache=False``: the same matrices from
    runtime ops (cos/sin in the program)."""
    import jax
    plan = plan_lib.make_plan(512)
    p1, p2 = plan.planes
    assert p1.shape == (16, 2, 32, 2, 32) and p2.shape == (2, 16, 2, 16)
    assert p1.dtype == np.float32
    j1, k1, j2 = 3, 5, 7
    w = np.exp(-2j * np.pi * (j1 * k1 / 32 + j2 * k1 / 512))
    np.testing.assert_allclose(
        p1[j2, :, j1, :, k1], [[w.real, w.imag], [-w.imag, w.real]],
        atol=1e-7)
    r1, r2 = plan.planes_jnp(rematerialize=True)
    np.testing.assert_allclose(np.asarray(r1), p1, atol=2e-6)
    np.testing.assert_allclose(np.asarray(r2), p2, atol=2e-6)
    x = jnp.zeros((2, 512, 4), jnp.float32)
    planned = str(jax.make_jaxpr(lambda v: lf.fft_planes(v, 1))(x))
    rebuilt = str(jax.make_jaxpr(
        lambda v: lf.fft_planes(v, 1, plan_cache=False))(x))
    assert " cos" not in planned and " cos" in rebuilt


@pytest.mark.parametrize("axis", [0, 1, 2])
def test_fft_planes_fields_of_a_batch_come_out_as_alone(axis):
    """``nbatch`` leading fields are dot batch dims: each field of a
    batch is the same contraction as the field alone, bitwise, at sizes
    where a longer row count would make the CPU backend pick another
    GEMM."""
    import jax
    shape = [4, 8, 4]
    shape[axis] = 16
    x = np.random.default_rng(axis).standard_normal([2] + shape).astype(
        np.float32)
    one = jax.jit(lambda p: lf.fft_planes(p, axis + 1))(x)
    many = jax.jit(lambda p: lf.fft_planes(p, axis + 2, nbatch=1))(
        np.stack([x] * 4, axis=1))
    for b in range(4):
        np.testing.assert_array_equal(np.asarray(many[:, b]), np.asarray(one))
