"""Spectral token mixer (FNet-style) — the LM-side consumer of CROFT.

y = Re( FFT_seq( FFT_model(x) ) )   (FNet, arXiv:2105.03824)

The model-dim FFT is always local.  The sequence-dim FFT, when the sequence
axis is sharded (context parallelism over the ``model`` mesh axis), runs the
paper's transpose pattern: all-to-all the hidden axis out / sequence axis in,
local FFT, all-to-all back — one round of CROFT's pencil machinery with the
same K-chunked overlap knob.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.core import local_fft
from repro.core import schedule as schedule_lib
from repro.core.distributed import FFTOptions


def _croft_stage(blk: jax.Array, *, comm_axis: str, split_axis: int,
                 concat_axis: int, chunk_axis: int,
                 opts: FFTOptions) -> jax.Array:
    """One K-chunked CROFT transpose (``schedule.run_stage`` with no FFT)
    inside ``shard_map``: the sequence FFTs here and the expert dispatch
    of ``models/moe_sharded.py``."""
    st = schedule_lib.Stage("ad-hoc", fft_axis=None, comm_axis=comm_axis,
                            split_axis=split_axis, concat_axis=concat_axis,
                            chunk_axis=chunk_axis)
    out = schedule_lib.run_stage(local_fft.to_planes(blk), st, -1, opts,
                                 off=1)
    return local_fft.from_planes(out)


def _fft_last(x: jax.Array) -> jax.Array:
    return local_fft.fft_matmul(x, sign=-1)


def spectral_mixer(x: jax.Array, *, seq_axis_name: Optional[str] = None,
                   mesh=None, batch_spec=None, overlap_k: int = 2):
    """x (B, S, D) real -> (B, S, D) real.

    ``seq_axis_name``: mesh axis the sequence is sharded over (None = local).
    """
    xc = x.astype(jnp.complex64)
    xc = _fft_last(xc)                      # hidden-dim FFT, always local
    if seq_axis_name is None:
        y = jnp.swapaxes(_fft_last(jnp.swapaxes(xc, 1, 2)), 1, 2)
    else:
        y = distributed_seq_fft(xc, seq_axis_name, mesh, batch_spec,
                                overlap_k)
    return jnp.real(y).astype(x.dtype)


def distributed_seq_fft(xc: jax.Array, axis_name: str, mesh, batch_spec,
                        overlap_k: int = 2) -> jax.Array:
    """FFT along a sharded sequence axis via the CROFT transpose pattern.

    local (B, S/P, D) --a2a--> (B, S, D/P) --fft(S)--> --a2a--> (B, S/P, D)
    """
    opts = FFTOptions(overlap_k=overlap_k)

    def body(blk):  # (B, S/P, D)
        blk = _croft_stage(blk, comm_axis=axis_name, split_axis=2,
                           concat_axis=1, chunk_axis=0, opts=opts)
        blk = jnp.moveaxis(_fft_last(jnp.moveaxis(blk, 1, -1)), -1, 1)
        blk = _croft_stage(blk, comm_axis=axis_name, split_axis=1,
                           concat_axis=2, chunk_axis=0, opts=opts)
        return blk

    spec = P(batch_spec, axis_name, None)
    return shard_map(body, mesh=mesh, in_specs=spec, out_specs=spec)(xc)
