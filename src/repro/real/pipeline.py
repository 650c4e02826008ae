"""Distributed packed r2c/c2r pipelines (pencil and slab decompositions).

The paper leaves r2c/c2r as future work (§8); this is the native path —
the embedding fallback lives in ``repro.core.rfft``.  Since the schedule
refactor the pipelines are *built*, not hardcoded: the functions below
return :class:`repro.core.schedule.Schedule` objects using the packed
stage ops (``PackTwo``/``UnpackTwo``/``RepackHalves``/``SplitPairs``),
and the entry points run them with the same executor as the complex
transform.  Layouts:

  real input    the decomposition's *spectral* layout (z fully local so
                the r2c stage runs first): pencil z-pencils
                (Nx/Py, Ny/Pz, Nz), slab z-slabs (Nx/P, Ny, Nz).  The
                real transform starts where the complex transform ends.
  packed        the shard-aligned half spectrum: (Nx, Ny, Nz/2) complex
  spectrum      in the decomposition's *natural* layout.  Bin 0 of the
                z axis carries the (real) DC and Nyquist planes folded
                into one complex plane (packing.py); bins 1..Nz/2-1 are
                the true spectrum.
  r2c output    (Nx, Ny, Nz//2 + 1), ``numpy.fft.rfftn``-compatible, in
                the z-local spectral layout — the packed body is
                resharded once (an out-of-body fused all-to-all of the
                half volume, ``Schedule.extra_comms``) so the odd-sized
                Nh axis is never sharded, then one (Nx, Ny)-plane
                Hermitian reconstruction (``unfold_dc_plane``) splits
                the folded DC/Nyquist plane.

Pencil forward stages (each overlapped with its all_to_all via the
K-chunking of ``schedule.run_stage``):

  1. pack two real z-pencils -> one complex pencil, FFT along z, unpack
     via Hermitian symmetry into the folded half spectrum   [stage 0]
  2. transpose z<->y over axes[1], FFT along y               [stage 1]
  3. transpose y<->x over axes[0], FFT along x               [stage 2]

The slab variant (ROADMAP "packed slab") pairs two x-lines instead —
local z-rfft, then the y FFT overlapped with the single z<->x transpose
of the half volume, then the x FFT — covering the 1-axis meshes where
the tuner previously had to fall back to the embedding.

Every transpose moves half the bytes of the c2c path and the z FFTs run
on half as many pencils — the ~2x first-stage bandwidth saving the
ROADMAP names, compounding with the spectral-layout trick (the packed
pipeline never pays restoring transposes).

The inverse runs the exact mirror and is algebraically exact: the
two-for-one split/merge is a linear bijection, so c2r(r2c(x)) == ifft
(fft(x)) up to the same rounding as the c2c path.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Mapping, Optional, Sequence, Union

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding

from jax import shard_map
from repro.core import schedule as schedule_lib
from repro.core.decomposition import Decomposition, _mesh_axis_sizes
from repro.core.distributed import FFTOptions, _norm_scale
from repro.core.schedule import (ExtraComm, PackTwo, RepackHalves, Schedule,
                                 SplitPairs, Stage, UnpackTwo, layout_for)
from repro.obs import scopes
from repro.real import packing

#: grid dim two real lines are paired along, per decomposition kind
PAIR_AXIS = {"pencil": 1, "slab": 0}


def packed_unsupported_reason(shape: Sequence[int], decomp: Decomposition,
                              mesh_or_sizes, opts: FFTOptions) -> Optional[str]:
    """None if the distributed packed pipeline supports the problem, else
    a human-readable reason (the planner and ``strategy="auto"`` use this
    to fall back to the embedding).  Pure arithmetic over axis sizes."""
    nx, ny, nz = shape[-3], shape[-2], shape[-1]
    if decomp is None:
        return "packed distributed path needs a Decomposition"
    if decomp.kind not in PAIR_AXIS:
        return (f"packed pipeline supports pencil and slab decompositions, "
                f"not {decomp.kind}")
    if nz % 2:
        return f"packed two-for-one needs even Nz, got {nz}"
    try:
        sizes = _mesh_axis_sizes(mesh_or_sizes)
        axis_sizes = decomp.axis_sizes(sizes)
    except (KeyError, TypeError) as e:
        return f"decomposition axes unresolvable on this mesh: {e}"
    if opts is not None and opts.transpose_impl in ("pairwise", "ring") and any(
            isinstance(a, tuple) for a in decomp.axes):
        return f"{opts.transpose_impl} transpose supports single mesh axes only"
    if decomp.kind == "slab":
        (p,) = axis_sizes
        if nx % p:
            return f"Nx={nx} not divisible by P={p} (z-slab input)"
        if (nx // p) % 2:
            return (f"local Nx={nx}//{p} is odd — cannot pair two x-lines "
                    "per complex transform")
        if (nz // 2) % p:
            return f"half spectrum Nz/2={nz // 2} not divisible by P={p}"
        return None
    py, pz = axis_sizes
    if nx % py:
        return f"Nx={nx} not divisible by Py={py} (z-pencil input)"
    if ny % pz:
        return f"Ny={ny} not divisible by Pz={pz} (z-pencil input)"
    if (ny // pz) % 2:
        return (f"local Ny={ny}//{pz} is odd — cannot pair two z-pencils "
                "per complex transform")
    if (nz // 2) % pz:
        return f"half spectrum Nz/2={nz // 2} not divisible by Pz={pz}"
    if ny % py:
        return f"Ny={ny} not divisible by Py={py} (y<->x transpose)"
    return None


# ---------------------------------------------------------------------------
# schedule builders.  Local axis order is (x, y, z); pairs ride on
# PAIR_AXIS[kind].  Input is the real spectral layout, body output the
# packed natural layout; the z-localizing epilogue reshard is recorded as
# an out-of-body ExtraComm (one fused all-to-all of the half volume).
# ---------------------------------------------------------------------------

def build_packed_forward(decomp: Decomposition) -> Schedule:
    """Real spectral-layout block -> packed natural-layout half spectrum."""
    pair = PAIR_AXIS[decomp.kind]
    layout_in = layout_for(decomp, "spectral", real=True)
    if decomp.kind == "pencil":
        ax_y, ax_z = decomp.axes
        stages = (
            Stage("pack+z-rfft+zy", fft_axis=2, impl_stage=0, comm_axis=ax_z,
                  split_axis=2, concat_axis=1, chunk_axis=0,
                  prologue=(PackTwo(pair),),
                  epilogue=(UnpackTwo(pair, impl_stage=0),)),
            Stage("y-fft+yx", fft_axis=1, impl_stage=1, comm_axis=ax_y,
                  split_axis=1, concat_axis=0, chunk_axis=2),
            Stage("x-fft", fft_axis=0, impl_stage=2),
        )
    else:  # slab: pair two x-lines, one z<->x transpose of the half volume
        # (the z-rfft chain overlaps the transpose, K-chunked along the
        # free y axis; y/x transforms run after, both local then)
        (ax_z,) = decomp.axes
        stages = (
            Stage("pack+z-rfft+zx", fft_axis=2, impl_stage=0, comm_axis=ax_z,
                  split_axis=2, concat_axis=0, chunk_axis=1,
                  prologue=(PackTwo(pair),),
                  epilogue=(UnpackTwo(pair, impl_stage=0),)),
            Stage("y-fft", fft_axis=1, impl_stage=1),
            Stage("x-fft", fft_axis=0, impl_stage=2),
        )
    sched = Schedule(f"{decomp.kind}/r2c/packed", -1, layout_in, stages)
    # the epilogue reshard moves the packed (half-volume) body output once
    return dataclasses.replace(
        sched, extra_comms=(ExtraComm("z-localize", sched.layout_out),))


def build_packed_inverse(decomp: Decomposition, nz: int) -> Schedule:
    """Packed natural-layout half spectrum -> real spectral-layout block."""
    pair = PAIR_AXIS[decomp.kind]
    layout_in = layout_for(decomp, "natural").with_den(2, mul=2)
    if decomp.kind == "pencil":
        ax_y, ax_z = decomp.axes
        stages = (
            Stage("x-ifft+xy", fft_axis=0, impl_stage=0, comm_axis=ax_y,
                  split_axis=0, concat_axis=1, chunk_axis=2),
            Stage("y-ifft+yz", fft_axis=1, impl_stage=1, comm_axis=ax_z,
                  split_axis=1, concat_axis=2, chunk_axis=0),
            Stage("repack+z-ifft+split", fft_axis=2, impl_stage=2,
                  prologue=(RepackHalves(pair, nz, impl_stage=2),),
                  epilogue=(SplitPairs(pair),)),
        )
    else:
        (ax_z,) = decomp.axes
        stages = (
            Stage("x-ifft+xz", fft_axis=0, impl_stage=0, comm_axis=ax_z,
                  split_axis=0, concat_axis=2, chunk_axis=1),
            Stage("y-ifft", fft_axis=1, impl_stage=1),
            Stage("repack+z-ifft+split", fft_axis=2, impl_stage=2,
                  prologue=(RepackHalves(pair, nz, impl_stage=2),),
                  epilogue=(SplitPairs(pair),)),
        )
    return Schedule(f"{decomp.kind}/c2r/packed", +1, layout_in, stages,
                    extra_comms=(ExtraComm("x-localize", layout_in),))


# ---------------------------------------------------------------------------
# DC/Nyquist plane fold/unfold — the only steps touching the odd
# (Nz//2 + 1)-sized axis, done once per transform on a single plane.
# ---------------------------------------------------------------------------

@scopes.role(scopes.RELAYOUT)
def unfold_dc_plane(packed: jax.Array) -> jax.Array:
    """Packed (..., Nx, Ny, Nz2) spectrum -> rfftn-style (..., Nx, Ny,
    Nz2 + 1).

    Bin 0 holds G = F2(DC_z) + i*F2(Nyq_z) with DC_z/Nyq_z real planes;
    the 2-D Hermitian split recovers both.  Runs at the global (traced)
    level so XLA shuffles only this one plane across shards.  The
    reconstruction is expressed over the trailing axes only, so a
    batched spectrum unfolds all its (Nx, Ny) planes in one vectorized
    pass — batched r2c never falls back to per-field dispatch.
    """
    g = packed[..., 0]
    rev = jnp.conj(packing.negate_freq(packing.negate_freq(g, -1), -2))
    dc = 0.5 * (g + rev)
    nyq = -0.5j * (g - rev)
    return jnp.concatenate([dc[..., None], packed[..., 1:], nyq[..., None]],
                           axis=-1)


def _hermitian_plane(p: jax.Array) -> jax.Array:
    """Project an (..., Nx, Ny) plane onto its 2-D-Hermitian part.

    ``numpy.fft.irfftn`` implicitly applies exactly this projection to
    the kz=0 and kz=Nyquist planes of a non-Hermitian half spectrum (its
    z-axis ``irfft`` drops the imaginary parts of those bins per pencil,
    and Re(ifft2(P)) == ifft2(Hermitian(P))).  For spectra that came
    from a real field the projection is the identity.
    """
    return 0.5 * (p + jnp.conj(packing.negate_freq(
        packing.negate_freq(p, -1), -2)))


@scopes.role(scopes.RELAYOUT)
def fold_dc_plane(y: jax.Array, nz: int) -> jax.Array:
    """Inverse of :func:`unfold_dc_plane`.

    The DC/Nyquist planes are first projected onto their Hermitian parts
    (a no-op for valid real-field spectra) so that arbitrary half
    spectra — e.g. derivative filters with a surviving Nyquist plane —
    invert exactly like ``numpy.fft.irfftn``.  Without the projection,
    anti-Hermitian content of the two planes would leak into each other
    through the complex fold.
    """
    nz2 = nz // 2
    g = _hermitian_plane(y[..., 0]) + 1j * _hermitian_plane(y[..., nz2])
    return jnp.concatenate([g[..., None], y[..., 1:nz2]], axis=-1)


def _negate_plane(p: jax.Array) -> jax.Array:
    """(kx, ky) -> (-kx, -ky) over the last two axes."""
    return packing.negate_freq(packing.negate_freq(p, -1), -2)


@scopes.role(scopes.RELAYOUT)
def unfold_dc_plane_planes(packed: jax.Array) -> jax.Array:
    """:func:`unfold_dc_plane` on planes: (2, ..., Nz2) -> (2, ..., Nz2 + 1),
    one elementwise pass (``packing.bins`` picks the two end bins)."""
    nz2 = packed.shape[-1]
    g = packed[..., 0]
    dc, nyq = packing.hermitian_halves(g, _negate_plane(g))
    k = packing.bins(nz2 + 1)
    return jnp.where(k == 0, dc[..., None],
                     jnp.where(k == nz2, nyq[..., None],
                               packing.pad_last(packed, 0, 1)))


@scopes.role(scopes.RELAYOUT)
def fold_dc_plane_planes(y: jax.Array, nz: int) -> jax.Array:
    """:func:`fold_dc_plane` on planes: (2, ..., Nh) -> (2, ..., Nz/2),
    one elementwise pass over the bins kept."""
    def hermitian(g):  # as _hermitian_plane
        return packing.hermitian_halves(g, _negate_plane(g))[0]

    nz2 = nz // 2
    dc, nyq = hermitian(y[..., 0]), hermitian(y[..., nz2])
    g = jnp.stack([dc[0] - nyq[1], dc[1] + nyq[0]])      # dc + i nyq
    return jnp.where(packing.bins(nz2) == 0, g[..., None], y[..., :nz2])


# ---------------------------------------------------------------------------
# public entry points
# ---------------------------------------------------------------------------

def real_input_spec(decomp: Decomposition):
    """PartitionSpec of the packed pipeline's real input (z-local spectral
    layout, pencil and slab alike)."""
    return decomp.spectral_spec()


def _with_batch_dims(spec, n: int):
    """A rank-3 PartitionSpec widened with ``n`` leading unsharded batch
    axes (velocity-component stacks and other vmapped field batches)."""
    from jax.sharding import PartitionSpec as P
    if n == 0:
        return spec
    return P(*((None,) * n), *spec)


@scopes.role(scopes.TRANSPOSE)
def constrain_sharding(y: jax.Array, sharding: NamedSharding) -> jax.Array:
    """Reshard ``y``: a sharding constraint under tracing, a device_put
    on concrete arrays (shared by the packed pipeline and core.rfft)."""
    if isinstance(y, jax.core.Tracer):
        return jax.lax.with_sharding_constraint(y, sharding)
    return jax.device_put(y, sharding)


def packed_rfft3d(x: jax.Array, mesh: Mesh, decomp: Decomposition,
                  opts: Optional[FFTOptions] = None,
                  norm: Optional[str] = None,
                  kspace_filter: Optional[jax.Array] = None,
                  fold_filter: bool = False) -> jax.Array:
    """Distributed packed r2c: real (Nx, Ny, Nz) -> (Nx, Ny, Nz//2 + 1)
    in the z-local spectral layout.

    ``kspace_filter`` (shaped like the output half spectrum) fuses the
    k-space multiply into the same jit, right after the plane unfold —
    the "unfolded epilogue" variant that works for any filter, including
    those with h(kz=0) != h(kz=Nyquist).

    Leading batch axes (velocity-component triples and the like) ride
    natively: a (B, Nx, Ny, Nz) input runs ONE schedule whose
    collectives move all B fields per launch and whose DC/Nyquist plane
    unfold reconstructs all B planes in a single pass — no per-field
    vmap dispatch (the executor offsets every axis index by the batch
    rank, ``run_schedule``'s ``off``).
    """
    if opts is None:
        opts = FFTOptions()
    if x.ndim < 3:
        raise ValueError("packed_rfft3d expects a (..., Nx, Ny, Nz) array")
    nbatch = x.ndim - 3
    reason = packed_unsupported_reason(x.shape, decomp, mesh, opts)
    if reason is not None:
        raise ValueError(f"packed r2c unsupported here: {reason}")
    scale = _norm_scale(x.shape, -1, norm)
    cdtype = jnp.result_type(x.dtype, jnp.complex64)
    # custom-vjp plans (repro.grad): the forward runs the same body +
    # one half-volume all-to-all bringing z local (the schedule's
    # recorded ExtraComm, so the odd-sized Nh axis stays unsharded and
    # the plane unfold needs no cross-z traffic) + plane unfold + norm
    # scale; the backward runs the adjoint schedule under the same opts
    from repro.grad import vjp as grad_vjp
    if kspace_filter is not None and fold_filter:
        # folded epilogue: multiply the *packed* half spectrum inside the
        # schedule, before the plane unfold — h must satisfy
        # h(kz=0) == h(kz=Nyquist) with that plane real and 2-D-even
        # (h[kx,ky] == h[-kx,-ky]); the filter's own Nyquist plane is
        # never read (and gets a zero cotangent under differentiation)
        with jax.named_scope(scopes.RELAYOUT):
            hp = kspace_filter[..., : x.shape[-1] // 2].astype(cdtype)
        plan = grad_vjp.packed_rfft_folded_plan(mesh, decomp, opts, scale,
                                                nbatch, hp.ndim - 3)
        return plan(x, hp)
    y = grad_vjp.packed_rfft_plan(mesh, decomp, opts, scale, nbatch)(x)
    if kspace_filter is not None:
        from repro.kernels import spectral_scale as ss
        out_sharding = NamedSharding(
            mesh, _with_batch_dims(decomp.spectral_spec(), nbatch))
        y = constrain_sharding(
            ss.spectral_scale(y, kspace_filter.astype(y.dtype)), out_sharding)
    return y


def packed_irfft3d(y: jax.Array, nz: int, mesh: Mesh, decomp: Decomposition,
                   opts: Optional[FFTOptions] = None,
                   norm: Optional[str] = None) -> jax.Array:
    """Distributed packed c2r: (..., Nx, Ny, Nz//2 + 1) -> real
    (..., Nx, Ny, Nz); leading batch axes ride natively (see
    :func:`packed_rfft3d`)."""
    if opts is None:
        opts = FFTOptions()
    if y.ndim < 3:
        raise ValueError("packed_irfft3d expects a (..., Nx, Ny, Nh) spectrum")
    nbatch = y.ndim - 3
    nx, ny = y.shape[-3], y.shape[-2]
    reason = packed_unsupported_reason((nx, ny, nz), decomp, mesh, opts)
    if reason is not None:
        raise ValueError(f"packed c2r unsupported here: {reason}")
    # custom-vjp plan (repro.grad): fold in the z-local layout (mirror of
    # the forward's epilogue), reshard the packed body back to natural
    # (the schedule's recorded ExtraComm), run the inverse body, scale
    from repro.grad import vjp as grad_vjp
    scale = _norm_scale((nx, ny, nz), +1, norm)
    return grad_vjp.packed_irfft_plan(mesh, decomp, nz, opts, scale,
                                      nbatch)(y)
