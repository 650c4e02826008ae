"""Real-to-complex / complex-to-real 3-D transforms.

The paper lists r2c/c2r as future work (§8).  Two strategies, dispatched
here (the stable entry points) and implemented in ``repro.real``:

``strategy="packed"``   the native path: two real z-pencils share one
    complex transform (two-for-one), the spectrum travels as exactly
    Nz/2 shard-aligned complex bins (Nyquist folded into DC), and every
    stage computes/moves half of what the c2c pipeline would.  See
    ``repro.real.pipeline`` for the layout contract (distributed input
    is *z-pencils*, ``Decomposition.spectral_spec()``).

``strategy="embed"``    cast to complex, run c2c, keep the non-redundant
    half of the last axis.  2x first-stage bandwidth waste, but valid
    for every decomposition/shape — the fallback and numerical oracle.

``strategy="auto"`` (default) picks packed wherever it is supported.
Both match ``numpy.fft.rfftn`` / ``irfftn`` semantics with axes in
(x, y, z) order.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import distributed, local_fft
from repro.core.decomposition import Decomposition
from repro.core.distributed import FFTOptions
from repro.obs import scopes
from repro import real as real_lib
# submodule-import form: resolves even while repro.real's own __init__ is
# still running (e.g. `import repro.real` pulls repro.core, which pulls
# this module, before repro.real has bound its `packing` attribute)
from repro.real import packing as _real_packing


def _is_multidevice(mesh) -> bool:
    return mesh is not None and math.prod(mesh.devices.shape) > 1


def _z_shard_count(decomp: Decomposition, mesh, layout: str) -> int:
    """How many ways the (global) z axis is sharded in the given layout."""
    spec = (decomp.partition_spec() if layout == "natural"
            else decomp.spectral_spec())
    entry = spec[2]
    if entry is None:
        return 1
    sizes = dict(mesh.shape)
    if isinstance(entry, tuple):
        return math.prod(sizes[a] for a in entry)
    return sizes[entry]


def _guarded_half_slice(y: jax.Array, nz: int, mesh, decomp, opts) -> jax.Array:
    """``y[..., : nz//2 + 1]`` that never materializes a cross-shard slice.

    In the natural output layout z is sharded, and the odd-sized half
    spectrum cannot tile those shards: silently slicing would make XLA
    gather (or unevenly pad) the spectrum.  Instead we reshard z to be
    local first (an all-to-all shuffle, no gather) and slice locally —
    which also honors ``Croft3D.output_sharding``'s contract that every
    r2c spectrum comes back in the z-local layout.
    """
    nh = nz // 2 + 1
    if (_is_multidevice(mesh) and decomp is not None
            and _z_shard_count(decomp, mesh, opts.output_layout) > 1):
        if decomp.kind in ("pencil", "slab"):
            target = decomp.spectral_spec()    # z local, x/y take the shards
        else:  # cell: no 3-axis layout keeps z local; replicate over z
            target = P(decomp.axes[0], decomp.axes[1], None)
        y = real_lib.constrain_sharding(y, NamedSharding(mesh, target))
    with jax.named_scope(scopes.RELAYOUT):
        return y[..., :nh]


def rfft3d(x: jax.Array, mesh=None, decomp: Optional[Decomposition] = None,
           opts: Optional[FFTOptions] = None,
           strategy: str = "auto", norm: Optional[str] = None,
           kspace_filter: Optional[jax.Array] = None,
           fold_filter: bool = False) -> jax.Array:
    """Real input (Nx, Ny, Nz) -> complex (Nx, Ny, Nz//2 + 1).

    Matches ``jnp.fft.rfftn`` with axes in (x, y, z) order (z contiguous,
    halved).  ``strategy``: "packed" | "embed" | "auto" (see module doc).
    ``norm``: None/"backward" (unscaled forward) | "ortho" (1/sqrt(N)).
    ``kspace_filter`` (shaped like the half spectrum) fuses a k-space
    multiply into the transform — the packed pipeline applies it right
    after the DC/Nyquist unfold, inside the same jit.  ``fold_filter``
    (packed distributed path only) moves the multiply *before* the
    unfold, onto the packed half spectrum inside the schedule — valid
    for filters with ``h(kz=0) == h(kz=Nyquist)``, that plane real and
    2-D-even (see ``repro.real.pipeline.packed_rfft3d``).
    NOTE the packed distributed input layout is the *spectral* layout
    (``decomp.spectral_spec()``: z-pencils / z-slabs), not the c2c
    natural layout.
    """
    if opts is None:
        opts = FFTOptions()
    if jnp.iscomplexobj(x):
        raise ValueError("rfft3d expects a real array")
    resolved = real_lib.resolve_strategy(strategy, x.shape, mesh, decomp, opts)
    if fold_filter and not (resolved == "packed" and _is_multidevice(mesh)
                            and kspace_filter is not None):
        raise ValueError("fold_filter=True needs a kspace_filter on the "
                         "distributed packed path (it folds the multiply "
                         "into the packed schedule)")
    if resolved == "packed":
        if not _is_multidevice(mesh):
            y = real_lib.local_rfft3d_packed(x, opts, norm=norm)
        else:
            return real_lib.packed_rfft3d(x, mesh, decomp, opts, norm=norm,
                                          kspace_filter=kspace_filter,
                                          fold_filter=fold_filter)
    else:
        nz = x.shape[-1]
        with jax.named_scope(scopes.RELAYOUT):
            xc = x.astype(jnp.complex64 if x.dtype != jnp.float64
                          else jnp.complex128)
        y = distributed.fft3d(xc, mesh, decomp, opts, norm=norm)
        y = _guarded_half_slice(y, nz, mesh, decomp, opts)
    if kspace_filter is not None:
        from repro.kernels import spectral_scale as ss
        y = ss.spectral_scale(y, kspace_filter.astype(y.dtype))
    return y


_negate_freq = _real_packing.negate_freq  # k -> (-k) mod N index map


def irfft3d(y: jax.Array, nz: int, mesh=None,
            decomp: Optional[Decomposition] = None,
            opts: Optional[FFTOptions] = None,
            strategy: str = "auto", norm: Optional[str] = None) -> jax.Array:
    """Inverse of :func:`rfft3d`; reconstructs the Hermitian half.

    F[kx, ky, kz] = conj(F[-kx mod Nx, -ky mod Ny, nz - kz]) for the
    missing bins kz in [nz//2 + 1, nz - 1].  ``norm``: None/"backward"
    (1/N) | "ortho" (1/sqrt(N)), matching :func:`rfft3d`.
    """
    if opts is None:
        opts = FFTOptions()
    shape = (y.shape[-3], y.shape[-2], nz)
    resolved = real_lib.resolve_strategy(strategy, shape, mesh, decomp, opts)
    if resolved == "packed":
        if not _is_multidevice(mesh):
            return real_lib.local_irfft3d_packed(y, nz, opts, norm=norm)
        return real_lib.packed_irfft3d(y, nz, mesh, decomp, opts, norm=norm)
    with jax.named_scope(scopes.RELAYOUT):
        body = y[..., 1: (nz + 1) // 2]       # kz' = 1 .. ceil(nz/2)-1
        tail = jnp.conj(body)
        tail = _negate_freq(tail, -3)         # -kx mod Nx
        tail = _negate_freq(tail, -2)         # -ky mod Ny
        tail = jnp.flip(tail, -1)             # ascending kz = nz-kz' order
        full = jnp.concatenate([y, tail], axis=-1)
    assert full.shape[-1] == nz, (full.shape, nz)
    x = distributed.ifft3d(full, mesh, decomp, opts, norm=norm)
    with jax.named_scope(scopes.RELAYOUT):
        return jnp.real(x)


def rfft3d_local(x: jax.Array) -> jax.Array:
    """Single-device r2c via the plan-based local transform (z-axis halved)."""
    return rfft3d(x, mesh=None)
