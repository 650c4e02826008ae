"""Pallas pack/unpack kernels for the ring/pairwise global transposes.

A P-rank ring (or pairwise) transpose moves P contiguous blocks of the
split axis to P peers and reassembles P received blocks along the concat
axis.  Rank r's send block for round s is the slice at ``(r + s) % P``
— a *rotated* block gather — and the received pieces land rotated by
``r`` the other way.  The executor used to express both sides as a
``dynamic_slice`` plus a full-size ``dynamic_update_slice`` per round:
P-1 full passes over the block just to shuffle it.

Both sides are really one data movement each: a cyclic rotation of the
P row-blocks by a rank-dependent shift.  :func:`rotate_blocks` does that
rotation in a single tiled pass — the Pallas kernel reads row-block
``(i + shift) % P`` and writes row-block ``i``, with the traced shift
(``jax.lax.axis_index``) carried as a scalar operand, so pack and unpack
each cost exactly one read + one write of the block:

  pack    rotate_blocks(x, split_axis, shift=idx)    then P static slices
  unpack  concatenate received pieces (static order), then
          rotate_blocks(y, concat_axis, shift=-idx)

Kernels follow the repo convention (``kernels/hermitian.py``): f32 plane
kernels, row-blocked grid, compiled on TPU and interpret mode elsewhere;
the blocks are the schedule executor's real/imaginary planes stacked on
axis 0 (``local_fft.to_planes``), which the kernel takes as they are.
Off-TPU the same data movements lower to the forms XLA CPU/GPU copy
fastest (raced head-to-head on the CI host): a static-slice
``lax.switch`` pack, an in-place ``dynamic_update_slice`` unpack, and a
doubled-buffer dynamic slice for :func:`rotate_blocks` itself — never
``jnp.roll``, whose traced-shift form lowers to a gather.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import backend


def _rotate_kernel(shift_ref, x_ref, o_ref):
    """Pure tile copy: the rotation lives entirely in the index maps."""
    del shift_ref
    o_ref[...] = x_ref[...]


def rotate_block_rows_planes(x: jax.Array, shift: jax.Array, n_blocks: int,
                             *, interpret: Optional[bool] = None):
    """(P, R, M) f32 planes stacked on axis 0 -> the same with each
    plane's ``n_blocks`` row-blocks cyclically rotated by ``shift``
    blocks (out block i = in block (i + shift) % n_blocks).  ``shift`` is
    a shape-(1,) int32 array and may be traced (the rank index inside
    ``shard_map``).

    The planes are viewed as (P, n_blocks, R / n_blocks, M) and each
    row-block is tiled over its rows and columns, so one window stays a
    few MiB at any block size (a whole row-block of a 1024^3 pencil is
    256 MiB, far past VMEM); a window holds that tile of every plane.
    The shift rides as a *scalar-prefetch* operand consumed by the input
    index map — grid step (i, j, k) fetches tile (j, k) of block
    ``(i + shift) % n_blocks`` — so the kernel body is a pure copy with
    no data-dependent indexing."""
    interpret = backend.resolve_interpret(interpret)
    planes, r, m = x.shape
    if r % n_blocks:
        raise ValueError(f"{r} rows not divisible into {n_blocks} blocks")
    block_rows = r // n_blocks
    # two windows (input, output) of every plane
    tile_rows = backend.pick_block_rows(block_rows,
                                        min(m, 16 * backend.LANES),
                                        2 * planes)
    tile_cols = backend.pick_block_cols(m, tile_rows, 2 * planes)
    tile = (planes, pl.Squeezed(), tile_rows, tile_cols)
    from jax.experimental.pallas import tpu as pltpu
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_blocks, block_rows // tile_rows, m // tile_cols),
        in_specs=[pl.BlockSpec(
            tile, lambda i, j, k, s_ref: (0, (i + s_ref[0]) % n_blocks, j, k))],
        out_specs=[pl.BlockSpec(tile, lambda i, j, k, s_ref: (0, i, j, k))],
    )
    shape4 = (planes, n_blocks, block_rows, m)
    (y,) = pl.pallas_call(
        _rotate_kernel,
        name="croft_rotate_blocks",
        grid_spec=grid_spec,
        out_shape=backend.f32_outputs(shape4, 1, x),
        interpret=interpret,
    )(shift, x.reshape(shape4))
    return y.reshape(planes, r, m)


def rotate_blocks(x: jax.Array, axis: int, shift, n_blocks: int, *,
                  use_pallas: Optional[bool] = None,
                  interpret: Optional[bool] = None) -> jax.Array:
    """Cyclically rotate the ``n_blocks`` equal blocks of ``x`` along
    ``axis`` by ``shift`` blocks (block i of the result is block
    (i + shift) % n_blocks of the input).  ``shift`` may be traced.

    ``x`` is a block in the schedule executor's form: planes stacked on
    axis 0 (``local_fft.to_planes``), which never rotates.  This is the
    fused pack/unpack primitive of the ring and pairwise transposes;
    ``use_pallas=None`` follows the repo convention (Pallas on TPU,
    plain jnp elsewhere — the fallback is a doubled-buffer dynamic
    slice, all contiguous copies).  The kernel takes f32 planes; other
    dtypes take the fallback.
    """
    if axis < 1:
        raise ValueError("axis 0 holds the planes; rotate another axis")
    if n_blocks == 1:
        return x
    extent = x.shape[axis]
    if extent % n_blocks:
        raise ValueError(
            f"axis {axis} extent {extent} not divisible by {n_blocks}")
    block = extent // n_blocks
    if use_pallas is None:
        use_pallas = backend.on_tpu()
    if not use_pallas or x.dtype != jnp.float32:
        # NOT jnp.roll: a *traced* shift makes roll lower to a gather
        # over the axis (index arithmetic per element).  Doubling the
        # array and taking one dynamic slice keeps every byte moved by
        # contiguous memcpy — 3 passes of plain copies beat 1 gather
        # pass by a wide margin on every backend.
        start = jnp.mod(jnp.asarray(shift, jnp.int32), n_blocks) * block
        doubled = jnp.concatenate([x, x], axis=axis)
        return jax.lax.dynamic_slice_in_dim(doubled, start, extent, axis)
    s = jnp.mod(jnp.asarray(shift, jnp.int32), n_blocks).reshape(1)
    moved = jnp.moveaxis(x, axis, 1)
    y = rotate_block_rows_planes(moved.reshape(x.shape[0], extent, -1), s,
                                 n_blocks, interpret=interpret)
    return jnp.moveaxis(y.reshape(moved.shape), 1, axis)


def unpack_pieces(pieces: list, axis: int, shift, *,
                  use_pallas: Optional[bool] = None) -> jax.Array:
    """The ring unpack: reassemble received pieces with block i of the
    result = ``pieces[(i + shift) % p]`` (``shift`` may be traced).

    On TPU: one concatenate + the fused :func:`rotate_blocks` pass.
    Elsewhere each piece lands with one ``dynamic_update_slice`` —
    placements the compiler performs in place (one total pass over the
    output), and unlike the pairwise emulation's chain the *ppermutes
    feeding them* stay mutually independent, so placement order never
    serializes the communication rounds.  (A p-way static-concat
    ``lax.switch`` and a doubled-buffer dynamic slice were raced
    head-to-head against this form on the CI host class; the in-place
    placement wins.)
    """
    p = len(pieces)
    if p == 1:
        return pieces[0]
    if use_pallas is None:
        use_pallas = backend.on_tpu()
    if use_pallas and pieces[0].dtype == jnp.float32:
        return rotate_blocks(jnp.concatenate(pieces, axis=axis), axis,
                             shift, p, use_pallas=use_pallas)
    block = pieces[0].shape[axis]
    out_shape = list(pieces[0].shape)
    out_shape[axis] = p * block
    out = jnp.zeros(out_shape, pieces[0].dtype)
    # pieces[m] is block (m - shift) % p of the result
    starts = jnp.mod(jnp.arange(p, dtype=jnp.int32)
                     - jnp.asarray(shift, jnp.int32), p) * block
    for m, piece in enumerate(pieces):
        out = jax.lax.dynamic_update_slice_in_dim(out, piece, starts[m], axis)
    return out


def pack_pieces(blk: jax.Array, axis: int, idx, n_blocks: int, *,
                use_pallas: Optional[bool] = None) -> list:
    """The ring/pairwise send pack: the ``n_blocks`` blocks of ``axis``
    as a list ordered by round (piece s is the block bound for rank
    ``(idx + s) % n_blocks``).

    On TPU this is the fused :func:`rotate_blocks` pass followed by free
    static slices; elsewhere a p-way ``lax.switch`` over static slice
    sets — the rank index takes only p values, so the compiler sees
    plain strided views (exactly one total pass over the block, no
    full-size intermediate, no dynamic indexing).
    """
    extent = blk.shape[axis]
    if extent % n_blocks:
        raise ValueError(
            f"axis {axis} extent {extent} not divisible by {n_blocks}")
    block = extent // n_blocks
    if use_pallas is None:
        use_pallas = backend.on_tpu()
    if use_pallas and blk.dtype == jnp.float32:
        packed = rotate_blocks(blk, axis, idx, n_blocks,
                               use_pallas=use_pallas)
        return jnp.split(packed, n_blocks, axis=axis)
    # p-way branch over static slice sets (see unpack_pieces): the
    # compiler sees plain strided views, not p dynamic slices
    p = n_blocks

    def cut(b, r):
        return tuple(
            jax.lax.slice_in_dim(b, ((r + s) % p) * block,
                                 ((r + s) % p + 1) * block, axis=axis)
            for s in range(p))

    branches = [(lambda b, r=r: cut(b, r)) for r in range(p)]
    return list(jax.lax.switch(jnp.mod(jnp.asarray(idx, jnp.int32), p),
                               branches, blk))
