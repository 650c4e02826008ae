"""Pallas kernel sweeps (interpret mode) against the pure-jnp oracles."""

import numpy as np
import jax.numpy as jnp
import pytest

from repro.kernels import fft_matmul_1d, spectral_scale_op
from repro.kernels.fft_matmul import fft4step_planes
from repro.kernels.ref import ref_fft_1d, ref_spectral_scale


@pytest.mark.parametrize("n", [64, 128, 256, 1024, 4096])
@pytest.mark.parametrize("b", [1, 3, 32])
def test_fft_matmul_kernel_shapes(n, b, rng):
    x = (rng.randn(b, n) + 1j * rng.randn(b, n)).astype(np.complex64)
    y = np.asarray(fft_matmul_1d(jnp.asarray(x)))
    ref = np.asarray(ref_fft_1d(jnp.asarray(x)))
    np.testing.assert_allclose(y, ref, atol=3e-4 * max(1, np.abs(ref).max()))


@pytest.mark.parametrize("sign", [-1, +1])
def test_fft_matmul_kernel_signs(sign, rng):
    x = (rng.randn(4, 256) + 1j * rng.randn(4, 256)).astype(np.complex64)
    y = np.asarray(fft_matmul_1d(jnp.asarray(x), sign=sign))
    ref = np.asarray(ref_fft_1d(jnp.asarray(x), sign=sign))
    np.testing.assert_allclose(y, ref, atol=3e-4 * np.abs(ref).max())


def test_fft_matmul_kernel_rank3(rng):
    x = (rng.randn(2, 5, 128) + 1j * rng.randn(2, 5, 128)).astype(np.complex64)
    y = np.asarray(fft_matmul_1d(jnp.asarray(x)))
    ref = np.fft.fft(x)
    np.testing.assert_allclose(y, ref, atol=3e-4 * np.abs(ref).max())


def test_kernel_block_row_edge(rng):
    """Batch not divisible by the default block: falls back to divisors."""
    x = (rng.randn(7, 64) + 1j * rng.randn(7, 64)).astype(np.complex64)
    y = np.asarray(fft_matmul_1d(jnp.asarray(x)))
    np.testing.assert_allclose(y, np.fft.fft(x), atol=2e-4 * np.abs(x).max() * 64)


def test_kernel_explicit_block_rows(rng):
    xr = rng.randn(8, 256).astype(np.float32)
    xi = rng.randn(8, 256).astype(np.float32)
    yr, yi = fft4step_planes(jnp.asarray(xr), jnp.asarray(xi), -1,
                             block_rows=2)
    ref = np.fft.fft(xr + 1j * xi)
    np.testing.assert_allclose(np.asarray(yr) + 1j * np.asarray(yi), ref,
                               atol=3e-4 * np.abs(ref).max())


def test_kernel_too_large_raises():
    from repro.kernels.fft_matmul import MAX_N
    n = MAX_N * 2
    xr = jnp.zeros((1, n), jnp.float32)
    with pytest.raises(ValueError):
        fft4step_planes(xr, xr)


@pytest.mark.parametrize("n", [128, 1024])
@pytest.mark.parametrize("alpha", [1.0, 0.25])
def test_spectral_scale_kernel(n, alpha, rng):
    x = (rng.randn(6, n) + 1j * rng.randn(6, n)).astype(np.complex64)
    h = (rng.randn(n) + 1j * rng.randn(n)).astype(np.complex64)
    y = np.asarray(spectral_scale_op(jnp.asarray(x), jnp.asarray(h), alpha))
    ref = np.asarray(ref_spectral_scale(jnp.asarray(x), jnp.asarray(h), alpha))
    np.testing.assert_allclose(y, ref, atol=1e-5 * max(1, np.abs(ref).max()))


def test_kernel_vs_distributed_pipeline_consistency(rng):
    """local_impl='pallas' inside the 3-D transform == jnp oracle."""
    from repro.core import fft3d, FFTOptions
    x = (rng.randn(16, 8, 8) + 1j * rng.randn(16, 8, 8)).astype(np.complex64)
    # pallas path requires pow-2 >= small sizes; use 16,8,8
    y = np.asarray(fft3d(jnp.asarray(x), opts=FFTOptions(local_impl="pallas")))
    ref = np.fft.fftn(x)
    np.testing.assert_allclose(y, ref, atol=5e-4 * np.abs(ref).max())


import jax


@pytest.mark.parametrize("cfg", [
    dict(b=2, sq=256, skv=256, h=4, kv=2, d=64, causal=True, win=None),
    dict(b=1, sq=128, skv=256, h=8, kv=8, d=32, causal=True, win=64),
    dict(b=1, sq=256, skv=256, h=2, kv=1, d=64, causal=False, win=None),
    dict(b=1, sq=128, skv=128, h=4, kv=4, d=128, causal=True, win=32),
])
def test_flash_attention_kernel(cfg, rng):
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.ref import ref_flash_attention
    q = jnp.asarray(rng.randn(cfg["b"], cfg["sq"], cfg["h"], cfg["d"])
                    .astype(np.float32))
    k = jnp.asarray(rng.randn(cfg["b"], cfg["skv"], cfg["kv"], cfg["d"])
                    .astype(np.float32))
    v = jnp.asarray(rng.randn(cfg["b"], cfg["skv"], cfg["kv"], cfg["d"])
                    .astype(np.float32))
    out = flash_attention(q, k, v, causal=cfg["causal"], window=cfg["win"],
                          q_block=128, kv_chunk=128)
    ref = ref_flash_attention(q, k, v, causal=cfg["causal"],
                              window=cfg["win"])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=5e-5)


def test_flash_attention_bf16(rng):
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.ref import ref_flash_attention
    q = jnp.asarray(rng.randn(1, 128, 2, 64), jnp.bfloat16)
    k = jnp.asarray(rng.randn(1, 128, 2, 64), jnp.bfloat16)
    v = jnp.asarray(rng.randn(1, 128, 2, 64), jnp.bfloat16)
    out = flash_attention(q, k, v, q_block=128, kv_chunk=64)
    ref = ref_flash_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=3e-2)
