"""Two-for-one pack/unpack primitives for real 3-D transforms.

The classic trick (Cooley/Tukey-era; P3DFFT and AccFFT both build their
r2c path on it): two real sequences a, b of length n cost ONE complex
FFT.  Pack c = a + i*b, transform C = FFT(c), and split with Hermitian
symmetry:

    A[k] = (C[k] + conj(C[-k mod n])) / 2
    B[k] = (C[k] - conj(C[-k mod n])) / (2i)
    C[k] = A[k] + i*B[k]                      (the exact inverse)

Here the two sequences are two real z-pencils of the local block, paired
along a local axis, so the distributed pipeline runs half as many z
transforms and every later stage moves half the bytes.

For even n the half spectrum has n/2 + 1 bins — one too many to stay
shard-aligned through the y/x transposes.  We use the packed
("halfcomplex" / CRAY-style) layout instead: DC and Nyquist bins of a
real transform are themselves real, so the Nyquist value rides in the
imaginary slot of bin 0 and the carried spectrum is exactly n/2 complex
bins — the same byte count as the real input, and divisible by the same
process counts.  Because the z-DC and z-Nyquist planes of a real field
are real (x, y)-planes, the folded bin stays a valid two-for-one packing
under the later y/x FFTs and is unfolded once, at the end, by a single
(Nx, Ny)-plane Hermitian reconstruction (``pipeline.unfold_dc_plane``).

All functions are pure jnp (they trace inside ``shard_map`` bodies).
The transforms run the planes forms at the end of this module; the
complex forms are their references.  ``use_pallas=True`` routes the
planes forms' folded unpack / Hermitian extend through the fused Pallas
kernels in ``repro.kernels.hermitian``.

Everything here is batch-transparent: the spectrum axis is always the
*last* axis and the pair axis an explicit (batch-offset) index, so
leading batch axes — vmapped velocity components, stacked fields —
vectorize through pack/unpack/repack in one pass (the Pallas paths
flatten every leading axis into kernel rows), and the distributed
pipeline's DC/Nyquist unfold amortizes across the whole batch instead
of falling back per-field.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.obs import scopes


def complex_dtype_for(real_dtype) -> jnp.dtype:
    """Spectrum dtype for a real input dtype (f32 -> c64, f64 -> c128)."""
    return (jnp.complex128 if jnp.dtype(real_dtype) == jnp.float64
            else jnp.complex64)


def real_dtype_for(complex_dtype) -> jnp.dtype:
    return (jnp.float64 if jnp.dtype(complex_dtype) == jnp.complex128
            else jnp.float32)


def negate_freq(a: jax.Array, axis: int = -1) -> jax.Array:
    """Index map k -> (-k) mod N along ``axis``: [0, N-1, N-2, ..., 1]."""
    return jnp.roll(jnp.flip(a, axis), 1, axis)


@scopes.role(scopes.RELAYOUT)
def pack_two(x: jax.Array, pair_axis: int) -> jax.Array:
    """Real block -> complex block, halved along ``pair_axis``.

    The first half along ``pair_axis`` becomes the real part, the second
    half the imaginary part (contiguous halves, not interleaved, so the
    unpacked spectra land back at their original positions with a single
    concatenate).  XLA fuses the two slices into the complex construction;
    there is no kernel-worthy work here.
    """
    m = x.shape[pair_axis]
    if m % 2:
        raise ValueError(f"pair axis extent {m} must be even to pack two-for-one")
    a = jax.lax.slice_in_dim(x, 0, m // 2, axis=pair_axis)
    b = jax.lax.slice_in_dim(x, m // 2, m, axis=pair_axis)
    return jax.lax.complex(a, b)


@scopes.role(scopes.RELAYOUT)
def unpack_two(C: jax.Array, pair_axis: int, *, nh: Optional[int] = None,
               fold: bool = False) -> jax.Array:
    """Split the FFT of a packed block into the two half spectra.

    ``C`` is the z-transform of ``pack_two(x)``; the result restores the
    original extent along ``pair_axis`` with the A spectra in the first
    half and the B spectra in the second (mirroring ``pack_two``).

    fold=False  keep ``nh`` bins per spectrum (n//2 + 1; works for odd n)
    fold=True   even n only: keep n//2 bins with the (real) Nyquist bin
                folded into the imaginary slot of the (real) DC bin —
                the shard-aligned layout the distributed pipeline carries.
    """
    n = C.shape[-1]
    if fold and n % 2:
        raise ValueError("fold=True needs an even transform size")
    rev = jnp.conj(negate_freq(C, -1))
    A = 0.5 * (C + rev)
    B = -0.5j * (C - rev)
    if fold:
        nz2 = n // 2

        def folded(S):
            # DC and Nyquist of a real transform are real; stash Nyquist
            # in DC's imaginary slot -> exactly nz2 bins, no bin lost
            s0 = jax.lax.complex(jnp.real(S[..., 0]), jnp.real(S[..., nz2]))
            return jnp.concatenate([s0[..., None], S[..., 1:nz2]], axis=-1)

        A, B = folded(A), folded(B)
    else:
        if nh is None:
            nh = n // 2 + 1
        A, B = A[..., :nh], B[..., :nh]
    return jnp.concatenate([A, B], axis=pair_axis)


@scopes.role(scopes.RELAYOUT)
def repack_halves(S: jax.Array, pair_axis: int, nz: int, *,
                  folded: bool = False) -> jax.Array:
    """Inverse of :func:`unpack_two`: rebuild the full packed z-spectrum.

    Given the two half spectra stacked along ``pair_axis`` (``folded``
    matching how they were produced), reconstruct the length-``nz``
    spectrum C[k] = A[k] + i*B[k] via Hermitian extension
    (C[nz-k] = conj(A[k] - i*B[k])), ready for one complex inverse FFT
    whose real/imaginary parts are the two real pencils.
    """
    m = S.shape[pair_axis]
    SA = jax.lax.slice_in_dim(S, 0, m // 2, axis=pair_axis)
    SB = jax.lax.slice_in_dim(S, m // 2, m, axis=pair_axis)
    if folded:
        # bin 0 carries (DC, Nyquist) of each spectrum in (real, imag)
        a0, b0 = SA[..., 0], SB[..., 0]
        c0 = jax.lax.complex(jnp.real(a0), jnp.real(b0))      # A[0] + i B[0]
        cn = jax.lax.complex(jnp.imag(a0), jnp.imag(b0))      # A[ny] + i B[ny]
        body = SA[..., 1:] + 1j * SB[..., 1:]                 # bins 1..nz/2-1
        tail = jnp.flip(jnp.conj(SA[..., 1:] - 1j * SB[..., 1:]), -1)
        return jnp.concatenate(
            [c0[..., None], body, cn[..., None], tail], axis=-1)
    # DC (and, for even nz, Nyquist) bins of a real transform are real;
    # keep only their real parts — numpy's irfft applies exactly this
    # projection, and it is the identity for valid real-field spectra.
    # Mixing in the imaginary parts via SA + i*SB would leak each
    # spectrum's anti-Hermitian content into the *other* pencil.
    nh = SA.shape[-1]
    c0 = jax.lax.complex(jnp.real(SA[..., 0]), jnp.real(SB[..., 0]))
    parts = [c0[..., None]]
    has_nyq = nz % 2 == 0 and nh - 1 == nz // 2
    body_hi = nh - 1 if has_nyq else nh
    parts.append(SA[..., 1:body_hi] + 1j * SB[..., 1:body_hi])
    if has_nyq:
        cn = jax.lax.complex(jnp.real(SA[..., -1]), jnp.real(SB[..., -1]))
        parts.append(cn[..., None])
    ntail = nz - nh
    t = SA[..., 1:1 + ntail] - 1j * SB[..., 1:1 + ntail]
    parts.append(jnp.flip(jnp.conj(t), -1))
    return jnp.concatenate(parts, axis=-1)


@scopes.role(scopes.RELAYOUT)
def split_pairs(c: jax.Array, pair_axis: int) -> jax.Array:
    """Complex block -> real block, doubled along ``pair_axis``.

    Inverse of :func:`pack_two`: the real parts are the first-half
    pencils, the imaginary parts the second half.
    """
    return jnp.concatenate([jnp.real(c), jnp.imag(c)], axis=pair_axis)


# ---------------------------------------------------------------------------
# the same steps on stacked planes, as the transforms carry their blocks
# (a leading plane axis: 2 = real/imaginary of a complex block, 1 = a
# real block; ``local_fft.to_planes``).  Axis indices count the plane
# axis; the complex forms above stay as their references.
# ---------------------------------------------------------------------------

@scopes.role(scopes.RELAYOUT)
def pack_two_planes(x: jax.Array, pair_axis: int) -> jax.Array:
    """:func:`pack_two` on planes: real (1, ...) -> complex (2, ...), the
    two halves along ``pair_axis`` becoming the two planes."""
    a = pair_axis % x.ndim
    m = x.shape[a]
    if m % 2:
        raise ValueError(f"pair axis extent {m} must be even to pack two-for-one")
    halves = x[0].reshape(x.shape[1:a] + (2, m // 2) + x.shape[a + 1:])
    return jnp.moveaxis(halves, a - 1, 0)


@scopes.role(scopes.RELAYOUT)
def split_pairs_planes(p: jax.Array, pair_axis: int) -> jax.Array:
    """:func:`split_pairs` on planes: complex (2, ...) -> real (1, ...)."""
    a = pair_axis % p.ndim
    q = jnp.moveaxis(p, 0, a - 1)
    return q.reshape(q.shape[:a - 1] + (2 * p.shape[a],) + q.shape[a + 1:])[None]


def _rows(p: jax.Array) -> tuple:
    """A planes block's two planes as (rows, bins) f32 arrays."""
    rows = math.prod(p.shape[1:-1])
    return p[0].reshape(rows, p.shape[-1]), p[1].reshape(rows, p.shape[-1])


def hermitian_halves(p: jax.Array, rev: jax.Array) -> tuple:
    """The two-for-one split on planes: with ``C = p`` and ``C~ = rev``
    (``C`` at the negated frequencies), the planes of
    ``A = (C + conj C~) / 2`` and ``B = (C - conj C~) / 2i``."""
    return (0.5 * jnp.stack([p[0] + rev[0], p[1] - rev[1]]),
            0.5 * jnp.stack([p[1] + rev[1], rev[0] - p[0]]))


def bins(n: int) -> jax.Array:
    """The bin index along the last axis.  The planes forms pick single
    bins with it (``where(bins(n) == 0, ...)``) instead of cutting out
    and concatenating one-bin slices, so each step stays one elementwise
    pass: on the TPU a one-bin slice pads to a whole lane tile."""
    return jax.lax.broadcasted_iota(jnp.int32, (n,), 0)


def pad_last(x: jax.Array, before: int, after: int) -> jax.Array:
    """``x`` padded with zeros along its last axis."""
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(before, after)])


def unpack_two_planes(p: jax.Array, pair_axis: int, *,
                      nh: Optional[int] = None, fold: bool = True,
                      use_pallas: bool = False) -> jax.Array:
    """:func:`unpack_two` on planes (``fold`` and ``nh`` as there).  The
    folded Pallas kernel reads and writes planes."""
    n = p.shape[-1]
    if fold and n % 2:
        raise ValueError("fold=True needs an even transform size")
    if fold and use_pallas and p.dtype == jnp.float32:
        from repro.kernels import hermitian
        with jax.named_scope(scopes.RELAYOUT):
            ar, ai, br, bi = hermitian.unpack_two_for_one_planes(*_rows(p))
            half = p.shape[:-1] + (n // 2,)
            return jnp.concatenate([jnp.stack([ar, ai]).reshape(half),
                                    jnp.stack([br, bi]).reshape(half)],
                                   axis=pair_axis)
    nh = n // 2 + 1 if fold or nh is None else nh
    with jax.named_scope(scopes.RELAYOUT):
        # C at the negated bins 0, n-1, ..., n-nh+1 (only the bins kept
        # are read twice): the flipped tail, shifted in by one
        k = bins(nh)
        rev = jnp.where(k == 0, p[..., :1],
                        pad_last(jnp.flip(p[..., n - nh + 1:], -1), 1, 0))
        A, B = hermitian_halves(p[..., :nh], rev)
        if fold:
            # DC and Nyquist of a real transform are real: Nyquist rides
            # in DC's imaginary plane -> exactly n/2 bins, none lost
            nz2 = n // 2
            A, B = (jnp.stack([S[0, ..., :nz2],
                               jnp.where(bins(nz2) == 0, S[0, ..., nz2:],
                                         S[1, ..., :nz2])])
                    for S in (A, B))
        return jnp.concatenate([A, B], axis=pair_axis)


def repack_halves_planes(p: jax.Array, pair_axis: int, nz: int, *,
                         folded: bool = True,
                         use_pallas: bool = False) -> jax.Array:
    """:func:`repack_halves` on planes (``folded`` as there).  The folded
    Pallas kernel reads and writes planes."""
    m = p.shape[pair_axis]
    with jax.named_scope(scopes.RELAYOUT):
        sa = jax.lax.slice_in_dim(p, 0, m // 2, axis=pair_axis)
        sb = jax.lax.slice_in_dim(p, m // 2, m, axis=pair_axis)
    if folded and use_pallas and p.dtype == jnp.float32:
        from repro.kernels import hermitian
        with jax.named_scope(scopes.RELAYOUT):
            cr, ci = hermitian.hermitian_extend_planes(*_rows(sa), *_rows(sb))
            return jnp.stack([cr, ci]).reshape(sa.shape[:-1] + (nz,))
    ar, ai, br, bi = sa[0], sa[1], sb[0], sb[1]
    nh = sa.shape[-1]
    k = bins(nh)
    with jax.named_scope(scopes.RELAYOUT):
        # C[k] = A[k] + iB[k] up to the middle, conj(A - iB)[nz - k] past it
        tail_r, tail_i = ar + bi, br - ai
        if folded:
            # bin 0 carries (DC, Nyquist) of each spectrum in its planes:
            # C[0] = (ar, br)[0] and C[nz/2] = (ai, bi)[0]
            head = jnp.stack([jnp.where(k == 0, ar, ar - bi),
                              jnp.where(k == 0, br, ai + br)])
            tail = jnp.stack([
                jnp.where(k == 0, ai[..., :1],
                          pad_last(jnp.flip(tail_r[..., 1:], -1), 1, 0)),
                jnp.where(k == 0, bi[..., :1],
                          pad_last(jnp.flip(tail_i[..., 1:], -1), 1, 0))])
        else:
            # DC (and, for even nz, Nyquist) keep their real parts only,
            # as numpy's irfft does (see repack_halves)
            real = k == 0
            if nz % 2 == 0 and nh - 1 == nz // 2:
                real = real | (k == nh - 1)
            head = jnp.stack([jnp.where(real, ar, ar - bi),
                              jnp.where(real, br, ai + br)])
            tail = jnp.flip(jnp.stack([tail_r, tail_i])[..., 1:1 + nz - nh],
                            -1)
        return jnp.concatenate([head, tail], axis=-1)
