"""repro.real — first-class real-to-complex / complex-to-real transforms.

CROFT lists r2c/c2r as future work (§8); P3DFFT (arXiv:1905.02803) and
AccFFT (arXiv:1506.07933) treat real transforms as a native problem
class with a Hermitian-halved spectrum.  This subsystem does the same
for the JAX/XLA port, with two strategies:

  "packed"  the two-for-one trick (``packing.py``): two real z-pencils
            share one complex z transform, the spectrum is carried as
            exactly Nz/2 shard-aligned complex bins (Nyquist folded
            into DC), and every transpose/FFT stage after the first
            moves/computes half of what the c2c pipeline would
            (``pipeline.py``).  Pallas kernels for the hot unpack /
            Hermitian-extend steps live in ``repro.kernels.hermitian``.
  "embed"   cast real -> complex, run c2c, keep the non-redundant half
            (``repro.core.rfft``).  2x first-stage bandwidth waste, but
            valid for every decomposition/shape — it is the fallback
            and the numerical oracle for the packed path.

``resolve_strategy`` picks between them ("auto"); the autotuner treats
the choice as a search dimension (``repro.tuning`` with
``problem="r2c"``), and ``Croft3D(..., problem="r2c")`` /
``Croft3D.tuned(..., problem="r2c")`` expose planned real transforms.

Public entry points: ``repro.core.rfft.rfft3d/irfft3d(strategy=...)``.
"""

from __future__ import annotations

import math
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from repro.core import local_fft
from repro.core.decomposition import Decomposition
from repro.core.distributed import FFTOptions, _norm_scale
from repro.obs import scopes
from repro.real import packing
from repro.real.pipeline import (build_packed_forward, build_packed_inverse,
                                 constrain_sharding, packed_irfft3d,
                                 packed_rfft3d, packed_unsupported_reason,
                                 real_input_spec, unfold_dc_plane,
                                 fold_dc_plane)

STRATEGIES = ("auto", "packed", "embed")


def _choose_pair_axis(nx: int, ny: int) -> Optional[int]:
    """Axis to pair z-pencils along on a single device: prefer y (keeps
    x contiguous for the later transforms), fall back to x."""
    if ny % 2 == 0:
        return -2
    if nx % 2 == 0:
        return -3
    return None


def packed_local_reason(shape: Sequence[int]) -> Optional[str]:
    """None if the single-device packed path supports ``shape``."""
    nx, ny = shape[-3], shape[-2]
    if _choose_pair_axis(nx, ny) is None:
        return (f"no even axis to pair z-pencils along (Nx={nx}, Ny={ny} "
                "both odd)")
    return None


def local_rfft3d_packed(x: jax.Array, opts: Optional[FFTOptions] = None,
                        norm: Optional[str] = None) -> jax.Array:
    """Single-device packed r2c: real (..., Nx, Ny, Nz) -> (..., Nx, Ny, Nh).

    Works for odd Nz too (the fold-free two-for-one keeps all Nh bins —
    there is no shard alignment to preserve on one device).
    """
    if opts is None:
        opts = FFTOptions()
    nx, ny, nz = x.shape[-3], x.shape[-2], x.shape[-1]
    reason = packed_local_reason(x.shape)
    if reason is not None:
        raise ValueError(f"packed r2c unsupported here: {reason}")
    pair_axis = _choose_pair_axis(nx, ny)
    fold = nz % 2 == 0  # odd Nz has no Nyquist bin; carry all Nh bins
    with scopes.stage("pack+z-rfft"):
        c = packing.pack_two(x, pair_axis)
        C = local_fft.fft_1d(c, -1, -1, impl=opts.stage_impl(0),
                             plan_cache=opts.plan_cache)
        S = packing.unpack_two(C, pair_axis, nh=nz // 2 + 1, fold=fold,
                               use_pallas=opts.stage_impl(0) == "pallas")
    with scopes.stage("y-fft"):
        S = local_fft.fft_1d(S, -2, -1, impl=opts.stage_impl(1),
                             plan_cache=opts.plan_cache)
    with scopes.stage("x-fft"):
        S = local_fft.fft_1d(S, -3, -1, impl=opts.stage_impl(2),
                             plan_cache=opts.plan_cache)
    # the fold stays valid under the (linear) y/x transforms; unfold the
    # DC/Nyquist plane once, at the end, like the distributed pipeline
    with scopes.stage("epilogue"):
        y = unfold_dc_plane(S) if fold else S
        scale = _norm_scale((nx, ny, nz), -1, norm)
        if scale is None:
            return y
        with jax.named_scope(scopes.SCALE):
            return y * jnp.asarray(scale, y.dtype)


def local_irfft3d_packed(y: jax.Array, nz: int,
                         opts: Optional[FFTOptions] = None,
                         norm: Optional[str] = None) -> jax.Array:
    """Single-device packed c2r: (..., Nx, Ny, Nh) -> real (..., Nx, Ny, Nz)."""
    if opts is None:
        opts = FFTOptions()
    nx, ny = y.shape[-3], y.shape[-2]
    reason = packed_local_reason((nx, ny, nz))
    if reason is not None:
        raise ValueError(f"packed c2r unsupported here: {reason}")
    pair_axis = _choose_pair_axis(nx, ny)
    fold = nz % 2 == 0
    with scopes.stage("prologue"):
        t = fold_dc_plane(y, nz) if fold else y
    with scopes.stage("x-ifft"):
        t = local_fft.fft_1d(t, -3, +1, impl=opts.stage_impl(0),
                             plan_cache=opts.plan_cache)
    with scopes.stage("y-ifft"):
        t = local_fft.fft_1d(t, -2, +1, impl=opts.stage_impl(1),
                             plan_cache=opts.plan_cache)
    with scopes.stage("repack+z-ifft+split"):
        C = packing.repack_halves(t, pair_axis, nz, folded=fold,
                                  use_pallas=opts.stage_impl(2) == "pallas")
        c = local_fft.fft_1d(C, -1, +1, impl=opts.stage_impl(2),
                             plan_cache=opts.plan_cache)
        x = packing.split_pairs(c, pair_axis)
    with scopes.stage("epilogue"), jax.named_scope(scopes.SCALE):
        return x * jnp.asarray(_norm_scale((nx, ny, nz), +1, norm), x.dtype)


def unsupported_reason(shape: Sequence[int], mesh, decomp,
                       opts: Optional[FFTOptions]) -> Optional[str]:
    """Why the packed strategy cannot run this problem (None = it can)."""
    if mesh is None or math.prod(mesh.devices.shape) == 1:
        return packed_local_reason(shape)
    return packed_unsupported_reason(shape, decomp, mesh,
                                     opts or FFTOptions())


def resolve_strategy(strategy: Optional[str], shape: Sequence[int], mesh,
                     decomp, opts: Optional[FFTOptions]) -> str:
    """Resolve "auto" to "packed"/"embed"; validate explicit choices.

    Explicitly requesting "packed" on an unsupported problem raises with
    the reason; "auto" silently falls back to the embedding (which is
    always valid wherever the c2c pipeline is).
    """
    strategy = strategy or "auto"
    if strategy not in STRATEGIES:
        raise ValueError(f"strategy must be one of {STRATEGIES}, got {strategy!r}")
    if strategy == "embed":
        return "embed"
    reason = unsupported_reason(shape, mesh, decomp, opts)
    if reason is None:
        return "packed"
    if strategy == "packed":
        raise ValueError(f"packed r2c unsupported here: {reason}")
    return "embed"


__all__ = [
    "STRATEGIES", "build_packed_forward", "build_packed_inverse",
    "constrain_sharding", "fold_dc_plane", "local_irfft3d_packed",
    "local_rfft3d_packed", "packed_irfft3d", "packed_local_reason",
    "packed_rfft3d", "packed_unsupported_reason", "packing",
    "real_input_spec", "resolve_strategy", "unfold_dc_plane",
    "unsupported_reason",
]
