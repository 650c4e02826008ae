"""The plain reference: 3-D DFTs as dense matrix products, one axis at a
time, in ``jax.numpy`` only.

Nothing here comes from the program under test.  A length-n DFT along one
axis is one product with the n x n matrix ``exp(sign 2 pi i j k / n)``
(O(n^2) per line, not a fast transform), split into real products so that
the precision of every product is explicit:

- ``"highest"``: ``Precision.HIGHEST``, float32 products, the precision the
  program states for its own DFT products;
- ``"high"``: three bfloat16 passes (hi*hi + hi*lo + lo*hi, each operand
  split into hi, its leading bfloat16 bits, and lo = bf16(a - hi),
  products summed in float32),
  what ``Precision.HIGH`` does on the MXU.  Spelled out rather than asked
  for, so that it computes the same on every backend.  It is the control:
  the reference one precision step below what the configuration states.

Forward transforms are unnormalised and inverses carry 1/N, as numpy's
``fftn``/``ifftn``/``rfftn``/``irfftn`` do.  Each axis is transformed a
few slices at a time (``chunks``), so that a field of 2 GiB per chip needs
little more than its output besides.  On a mesh the field is in the
natural pencil layout (axis 0 whole, axes 1 and 2 sharded); axes 1 and 2
are made whole in turn by ``jax.lax.all_to_all`` and the result goes back
to the input's layout.  The tests hold this against ``numpy.fft``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

PRECISIONS = ("highest", "high")
CHUNKS = 8


def dft_matrix(n: int, sign: int, out: int | None = None) -> tuple:
    """(re, im) of W[j, k] = exp(sign 2 pi i j k / n), j < n, k < out, in
    float64, with the phase reduced mod n in integers first."""
    out = n if out is None else out
    jk = (np.arange(n)[:, None] * np.arange(out)[None, :]) % n
    ang = sign * 2.0 * np.pi * jk / n
    return np.cos(ang), np.sin(ang)


def _split_bf16(a):
    """a = hi + lo + (rounding of lo), hi and lo in bfloat16.  hi is ``a``
    with its low 16 bits cleared, so it is exact in bfloat16 and ``a - hi``
    is exact in float32: no pair of conversions that a compiler allowed
    excess precision could fold away."""
    bits = jax.lax.bitcast_convert_type(a, jnp.uint32)
    hi = jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                      jnp.float32)
    return hi.astype(jnp.bfloat16), (a - hi).astype(jnp.bfloat16)


def product(subscripts: str, a, w, precision: str):
    """Real ``jnp.einsum(subscripts, a, w)`` at ``precision``."""
    if precision == "highest":
        return jnp.einsum(subscripts, a, w,
                          precision=jax.lax.Precision.HIGHEST)
    if precision != "high":
        raise ValueError(f"precision must be one of {PRECISIONS}, got "
                         f"{precision!r}")
    a_hi, a_lo = _split_bf16(a)
    w_hi, w_lo = _split_bf16(w)

    def dot(x, y):
        return jnp.einsum(subscripts, x, y,
                          preferred_element_type=jnp.float32)

    return dot(a_hi, w_hi) + dot(a_hi, w_lo) + dot(a_lo, w_hi)


def dft_axis(re, im, axis: int, w: tuple, precision: str,
             chunks: int = CHUNKS) -> tuple:
    """(re, im) of the product of a 3-D block with the matrix ``w`` =
    (wr, wi) [n, m] along ``axis``; ``im=None`` for a real block.  Computed
    a slab of the largest other axis at a time."""
    wr, wi = (jnp.asarray(m, jnp.float32) for m in w)
    shape = list(re.shape)
    shape[axis] = wr.shape[1]
    other = max((a for a in range(3) if a != axis), key=lambda a: shape[a])
    chunks = math.gcd(shape[other], chunks)
    c = shape[other] // chunks
    letters = ["a", "b", "c"]
    src = "".join("n" if a == axis else letters[a] for a in range(3))
    dst = "".join("k" if a == axis else letters[a] for a in range(3))
    sub = f"{src},nk->{dst}"

    def body(s, carry):
        out_re, out_im = carry
        br = jax.lax.dynamic_slice_in_dim(re, s * c, c, other)
        r = product(sub, br, wr, precision)
        i = product(sub, br, wi, precision)
        if im is not None:
            bi = jax.lax.dynamic_slice_in_dim(im, s * c, c, other)
            r = r - product(sub, bi, wi, precision)
            i = i + product(sub, bi, wr, precision)
        return (jax.lax.dynamic_update_slice_in_dim(out_re, r, s * c, other),
                jax.lax.dynamic_update_slice_in_dim(out_im, i, s * c, other))

    zeros = jnp.zeros(shape, jnp.float32)
    return jax.lax.fori_loop(0, chunks, body, (zeros, zeros))


def _fft3_local(x, sign: int, precision: str):
    re, im = jnp.real(x), jnp.imag(x)
    for axis in range(3):
        re, im = dft_axis(re, im, axis, dft_matrix(x.shape[axis], sign),
                          precision)
    return jax.lax.complex(re, im)


def _fft3_pencil(blk, sign: int, precision: str, ay: str, az: str,
                 n: tuple):
    """One chip's part: its (Nx, Ny/Py, Nz/Pz) block in, the same block of
    the transform out."""
    def a2a(v, axis_name, split, concat):
        return jax.lax.all_to_all(v, axis_name, split, concat, tiled=True)

    re, im = jnp.real(blk), jnp.imag(blk)
    re, im = dft_axis(re, im, 0, dft_matrix(n[0], sign), precision)
    re, im = (a2a(v, ay, 0, 1) for v in (re, im))      # (Nx/Py, Ny, Nz/Pz)
    re, im = dft_axis(re, im, 1, dft_matrix(n[1], sign), precision)
    re, im = (a2a(v, az, 1, 2) for v in (re, im))      # (Nx/Py, Ny/Pz, Nz)
    re, im = dft_axis(re, im, 2, dft_matrix(n[2], sign), precision)
    re, im = (a2a(v, az, 2, 1) for v in (re, im))      # (Nx/Py, Ny, Nz/Pz)
    re, im = (a2a(v, ay, 1, 0) for v in (re, im))      # (Nx, Ny/Py, Nz/Pz)
    return jax.lax.complex(re, im)


def fft3(x, sign: int = -1, precision: str = "highest", mesh=None,
         spec=None):
    """3-D complex DFT of ``x`` (complex64); ``sign=+1`` is the inverse,
    with 1/N.  On a ``mesh``, ``spec`` is (None, y-axis, z-axis), the
    natural pencil layout, kept on output."""
    if mesh is None:
        y = _fft3_local(x, sign, precision)
    else:
        if spec[0] is not None or None in spec[1:]:
            raise ValueError(f"the reference takes the natural pencil "
                             f"layout (None, y, z), got {spec}")
        body = functools.partial(_fft3_pencil, sign=sign,
                                 precision=precision, ay=spec[1],
                                 az=spec[2], n=x.shape)
        # check_vma off: the loops' zero-filled carries start replicated
        y = jax.shard_map(body, mesh=mesh, in_specs=P(*spec),
                          out_specs=P(*spec), check_vma=False)(x)
    if sign == +1:
        y = y / np.float32(x.size)
    return y


def rfft3(x, precision: str = "highest"):
    """3-D real-to-complex DFT, the (Nx, Ny, Nz//2 + 1) half spectrum."""
    nz = x.shape[-1]
    re, im = dft_axis(x, None, 2, dft_matrix(nz, -1, nz // 2 + 1), precision)
    for axis in (1, 0):
        re, im = dft_axis(re, im, axis, dft_matrix(x.shape[axis], -1),
                          precision)
    return jax.lax.complex(re, im)


def irfft3(y, nz: int, precision: str = "highest"):
    """Inverse of :func:`rfft3`, with 1/N: as ``numpy.fft.irfftn``, the
    imaginary parts of the kz = 0 and kz = Nz/2 planes are ignored."""
    re, im = jnp.real(y), jnp.imag(y)
    for axis in (0, 1):
        re, im = dft_axis(re, im, axis, dft_matrix(y.shape[axis], +1),
                          precision)
    # x[n] = sum_k w_k (Re X_k cos(2 pi k n / nz) - Im X_k sin(...))
    nh = nz // 2 + 1
    weight = np.full(nh, 2.0)
    weight[0] = 1.0
    if nz % 2 == 0:
        weight[-1] = 1.0
    cos, sin = dft_matrix(nz, +1, nh)          # [n, k]
    c = weight[:, None] * cos.T                # [k, n]
    s = weight[:, None] * sin.T
    x, _ = dft_axis(re, None, 2, (c, np.zeros_like(c)), precision)
    xs, _ = dft_axis(im, None, 2, (s, np.zeros_like(s)), precision)
    return (x - xs) / np.float32(y.shape[0] * y.shape[1] * nz)


@functools.lru_cache(maxsize=8)
def jitted(name: str, precision: str, mesh=None, spec=None, nz: int = 0):
    """One compiled reference entry, shared by every call with the same
    arguments (a mesh and spec are hashable)."""
    if name in ("fft3", "ifft3"):
        sign = -1 if name == "fft3" else +1
        return jax.jit(functools.partial(fft3, sign=sign, precision=precision,
                                         mesh=mesh, spec=spec))
    if name == "rfft3":
        return jax.jit(functools.partial(rfft3, precision=precision))
    if name == "irfft3":
        return jax.jit(functools.partial(irfft3, nz=nz, precision=precision))
    if name == "filtered_round_trip":
        return jax.jit(lambda x, h: irfft3(rfft3(x, precision) * h, nz,
                                           precision))
    raise ValueError(f"unknown reference entry {name!r}")
