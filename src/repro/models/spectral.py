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
from repro.core.distributed import _stage  # K-chunked (fft -> all_to_all)


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
    from repro.core.distributed import FFTOptions

    opts = FFTOptions(overlap_k=overlap_k)

    def body(blk):  # (B, S/P, D)
        blk = _stage(blk, fft_axis=None, comm_axis=axis_name, split_axis=2,
                     concat_axis=1, chunk_axis=0, sign=-1, opts=opts)
        blk = jnp.moveaxis(_fft_last(jnp.moveaxis(blk, 1, -1)), -1, 1)
        blk = _stage(blk, fft_axis=None, comm_axis=axis_name, split_axis=1,
                     concat_axis=2, chunk_axis=0, sign=-1, opts=opts)
        return blk

    spec = P(batch_spec, axis_name, None)
    return shard_map(body, mesh=mesh, in_specs=spec, out_specs=spec)(xc)


# --------------------------------------------------------------------------
# Learned spectral filter — the CROFT-side training workload
# --------------------------------------------------------------------------
#
# A two-parameter "spectral layer" over a distributed 3-D field:
#
#     y_hat(theta; x) = F( gate . x ) . filter
#
# with a learnable real-space gate (full grid) and a learnable k-space
# filter (half spectrum for r2c plans, full for c2c).  The transform is
# a planned Croft3D: the k-space multiply fuses as the plan's spectral
# epilogue (``forward_filtered``) and gradients replay the *adjoint
# schedule* (``repro.grad``) instead of XLA differentiating through
# shard_map collectives.  This is the workload ``tuned(grad=True)``
# plans for and ``benchmarks/train_bench.py`` gates.


def spectral_filter_shapes(plan) -> tuple:
    """(gate shape, filter shape) for a plan's learned spectral layer."""
    return tuple(plan.shape), tuple(plan.spectrum_shape)


def init_spectral_filter_params(key, plan, scale: float = 0.0,
                                dtype=jnp.float32):
    """Near-identity init: gate = 1 + scale*eps, filter = 1 + scale*eps.

    Real parameters in both domains (a real filter is the common
    physical case — attenuation per mode); ``scale=0`` gives the exact
    identity layer, useful as a deterministic oracle start.
    """
    gshape, fshape = spectral_filter_shapes(plan)
    kg, kf = jax.random.split(key)
    dt = jnp.dtype(dtype)
    return {
        "gate": (jnp.ones(gshape, dt)
                 + scale * jax.random.normal(kg, gshape, dt)),
        "filter": (jnp.ones(fshape, dt)
                   + scale * jax.random.normal(kf, fshape, dt)),
    }


def place_spectral_filter_params(plan, params):
    """Shard the layer's params the way the plan wants its operands: the
    gate with the input field, the filter with the output spectrum."""
    if plan.mesh is None:
        return params
    return {
        "gate": jax.device_put(params["gate"], plan.input_sharding),
        "filter": jax.device_put(params["filter"], plan.output_sharding),
    }


def spectral_filter_apply(plan, params, x: jax.Array) -> jax.Array:
    """``F(gate . x) . filter`` through the plan's fused epilogue."""
    gated = (params["gate"] * x).astype(plan.input_dtype)
    h = params["filter"].astype(plan.dtype)
    return plan.forward_filtered(gated, h)
