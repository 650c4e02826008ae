"""Pallas TPU kernel: fused causal/windowed GQA attention (flash-style).

The §Perf analysis shows every dense train cell is memory-bound on
attention-score round trips: the pure-XLA blockwise path streams the
(Sq x Skv) f32 scores through HBM several times per layer.  This kernel
keeps the whole online-softmax chain in VMEM: per (batch, q-head, q-block)
grid cell it loads one q block and the matching GQA kv head's K/V, loops
over kv chunks with running (m, l, acc), and writes only the (BQ, D)
output — one HBM read per operand, one write per result.

Forward only (serving + projection for training-fwd); the train path keeps
the XLA blockwise implementation whose backward is autodiff'd.
Validated in interpret mode against ``ref.ref_flash_attention``; compiled
on TPU (``kernels/backend.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import backend

NEG_INF = -2.0 ** 30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, kv_chunk: int, causal: bool,
                  window, bq: int, scale: float):
    """One grid cell: q (BQ, D) vs full K/V (Skv, D) for its kv head."""
    qi = pl.program_id(2)
    skv = k_ref.shape[-2]
    dv = v_ref.shape[-1]
    q = q_ref[0, 0].astype(jnp.float32) * scale
    n_chunks = skv // kv_chunk

    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)

    def body(c, carry):
        m_prev, l_prev, acc = carry
        # a chunk is sliced from the ref: Mosaic lowers no dynamic slice
        # of a loaded value
        rows = pl.ds(pl.multiple_of(c * kv_chunk, kv_chunk), kv_chunk)
        k = k_ref[0, 0, rows, :].astype(jnp.float32)
        v = v_ref[0, 0, rows, :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        k_pos = c * kv_chunk + jax.lax.broadcasted_iota(
            jnp.int32, (1, kv_chunk), 1)
        mask = jnp.ones((bq, kv_chunk), jnp.bool_)
        if causal:
            mask = mask & (k_pos <= q_pos)
        if window is not None:
            mask = mask & (q_pos - k_pos < window)
        s = jnp.where(mask, s, NEG_INF)
        m_cur = jnp.max(s, axis=1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=1)
        acc = acc * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc

    m0 = jnp.full((bq,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    a0 = jnp.zeros((bq, dv), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, n_chunks, body, (m0, l0, a0))
    out = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)
    o_ref[0, 0] = out


def flash_attention(q, k, v, *, causal: bool = True, window=None,
                    scale=None, q_block: int = 128, kv_chunk: int = 128,
                    interpret: bool | None = None):
    """q (B, Sq, H, D) · k,v (B, Skv, KV, D) -> (B, Sq, H, Dv).

    H % KV == 0 (GQA);  Sq % q_block == 0;  Skv % kv_chunk == 0.
    """
    b, sq, h, d = q.shape
    _, skv, kvh, dv = v.shape
    assert h % kvh == 0 and sq % q_block == 0 and skv % kv_chunk == 0
    g = h // kvh
    scale = scale if scale is not None else d ** -0.5

    # layout: heads to the front so each grid cell reads contiguous slabs
    qt = jnp.moveaxis(q, 2, 1)      # (B, H, Sq, D)
    kt = jnp.moveaxis(k, 2, 1)      # (B, KV, Skv, D)
    vt = jnp.moveaxis(v, 2, 1)

    kernel = functools.partial(
        _flash_kernel, kv_chunk=kv_chunk, causal=causal, window=window,
        bq=q_block, scale=scale)
    out = pl.pallas_call(
        kernel,
        name="flash_attention",
        grid=(b, h, sq // q_block),
        in_specs=[
            pl.BlockSpec((1, 1, q_block, d),
                         lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, skv, d),
                         lambda bi, hi, qi, g=g: (bi, hi // g, 0, 0)),
            pl.BlockSpec((1, 1, skv, dv),
                         lambda bi, hi, qi, g=g: (bi, hi // g, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, q_block, dv),
                               lambda bi, hi, qi: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, dv), q.dtype),
        interpret=backend.resolve_interpret(interpret),
    )(qt[:, :, :, :], kt, vt)
    return jnp.moveaxis(out, 1, 2)
