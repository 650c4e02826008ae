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
                                 fold_dc_plane, fold_dc_plane_planes,
                                 unfold_dc_plane_planes)

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

    Runs on stacked real/imaginary planes (``local_fft.to_planes``) from
    the real input to the one conversion at exit; leading axes are
    independent fields (dot batch dims).  Works for odd Nz too (the
    fold-free two-for-one keeps all Nh bins — there is no shard
    alignment to preserve on one device).
    """
    if opts is None:
        opts = FFTOptions()
    nx, ny, nz = x.shape[-3], x.shape[-2], x.shape[-1]
    reason = packed_local_reason(x.shape)
    if reason is not None:
        raise ValueError(f"packed r2c unsupported here: {reason}")
    pair_axis = _choose_pair_axis(nx, ny)
    fold = nz % 2 == 0  # odd Nz has no Nyquist bin; carry all Nh bins
    nd, nbatch = x.ndim + 1, x.ndim - 3  # planes rank, leading fields

    def fft(p, axis, stage):
        return local_fft.fft_along(p, axis, -1, impl=opts.stage_impl(stage),
                                   nbatch=nbatch, plan_cache=opts.plan_cache)

    with scopes.stage("pack+z-rfft"):
        p = packing.pack_two_planes(x[None], pair_axis)
        p = fft(p, nd - 1, 0)
        p = packing.unpack_two_planes(
            p, pair_axis, nh=nz // 2 + 1, fold=fold,
            use_pallas=opts.stage_impl(0) == "pallas")
    with scopes.stage("y-fft"):
        p = fft(p, nd - 2, 1)
    with scopes.stage("x-fft"):
        p = fft(p, nd - 3, 2)
    # the fold stays valid under the (linear) y/x transforms; unfold the
    # DC/Nyquist plane once, at the end, like the distributed pipeline
    with scopes.stage("epilogue"):
        if fold:
            p = unfold_dc_plane_planes(p)
        scale = _norm_scale((nx, ny, nz), -1, norm)
        if scale is not None:
            with jax.named_scope(scopes.SCALE):
                p = p * jnp.asarray(scale, p.dtype)
        return local_fft.from_planes(p)


def local_irfft3d_packed(y: jax.Array, nz: int,
                         opts: Optional[FFTOptions] = None,
                         norm: Optional[str] = None) -> jax.Array:
    """Single-device packed c2r: (..., Nx, Ny, Nh) -> real (..., Nx, Ny, Nz).

    Converts to planes once, at entry, and ends on the real output with
    no conversion back (the mirror of :func:`local_rfft3d_packed`).
    """
    if opts is None:
        opts = FFTOptions()
    nx, ny = y.shape[-3], y.shape[-2]
    reason = packed_local_reason((nx, ny, nz))
    if reason is not None:
        raise ValueError(f"packed c2r unsupported here: {reason}")
    pair_axis = _choose_pair_axis(nx, ny)
    fold = nz % 2 == 0
    nd, nbatch = y.ndim + 1, y.ndim - 3

    def ifft(p, axis, stage):
        return local_fft.fft_along(p, axis, +1, impl=opts.stage_impl(stage),
                                   nbatch=nbatch, plan_cache=opts.plan_cache)

    with scopes.stage("prologue"):
        p = local_fft.to_planes(y)
        if fold:
            p = fold_dc_plane_planes(p, nz)
    with scopes.stage("x-ifft"):
        p = ifft(p, nd - 3, 0)
    with scopes.stage("y-ifft"):
        p = ifft(p, nd - 2, 1)
    with scopes.stage("repack+z-ifft+split"):
        p = packing.repack_halves_planes(
            p, pair_axis, nz, folded=fold,
            use_pallas=opts.stage_impl(2) == "pallas")
        p = ifft(p, nd - 1, 2)
        x = packing.split_pairs_planes(p, pair_axis)[0]
    scale = _norm_scale((nx, ny, nz), +1, norm)
    if scale is None:
        return x
    with scopes.stage("epilogue"), jax.named_scope(scopes.SCALE):
        return x * jnp.asarray(scale, x.dtype)


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
