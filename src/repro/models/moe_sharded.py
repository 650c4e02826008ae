"""Sharding-explicit MoE dispatch (the §Perf fix for the MoE cells).

GSPMD resolves the global sort/scatter dispatch of ``moe.moe_fwd`` by
replicating the (E·C, D) buffers and all-reducing them — 60 TB/device/step
on deepseek-v2 train_4k (EXPERIMENTS.md §Perf).  This module pins the
communication pattern down with ``shard_map``:

  mode "ep"  (E divisible by the model axis — DeepSeek 160e/16):
      tokens stay (data x model)-sharded; each shard dispatches its local
      tokens into a local (E, C_loc, D) buffer; ONE all-to-all over the
      model axis swaps the expert dim for the capacity dim (exactly a CROFT
      pencil transpose, reusing the K-chunked overlap machinery); experts
      compute on their shard; the reverse all-to-all restores token layout.

  mode "tp"  (E not divisible — Mixtral 8e/16):
      no token movement at all: every shard dispatches locally and computes
      ALL experts on its local tokens with ffn-dim-sharded weights; the
      only collective is the psum of the down-projection output.

Both modes keep the router numerics of the reference implementation
(tests assert equality vs ``moe.moe_fwd``).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.distributed import FFTOptions
from repro.models.config import MoESpec
from repro.models.layers import ffn_fwd
from repro.models.spectral import _croft_stage


def _local_dispatch(xt, router_w, m: MoESpec, cap: int):
    """Shared shard-local dispatch: tokens (T,D) -> buf (E, C, D) + combine
    metadata.  Identical numerics to moe.moe_fwd's global dispatch, applied
    to the shard's local tokens."""
    t, d = xt.shape
    e, k = m.n_experts, m.top_k
    logits = xt.astype(jnp.float32) @ router_w.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, topk_idx = jax.lax.top_k(probs, k)
    gate_vals = gate_vals / jnp.clip(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)
    flat_e = topk_idx.reshape(-1)
    order = jnp.argsort(flat_e)
    sorted_e = flat_e[order]
    counts = jnp.bincount(flat_e, length=e)
    starts = jnp.cumsum(counts) - counts
    pos_in_e = jnp.arange(t * k) - starts[sorted_e]
    keep = pos_in_e < cap
    slot = sorted_e * cap + jnp.clip(pos_in_e, 0, cap - 1)
    token_of = order // k
    buf = jnp.zeros((e * cap, d), xt.dtype)
    buf = buf.at[jnp.where(keep, slot, e * cap)].set(
        xt[token_of], mode="drop")
    return buf.reshape(e, cap, d), (keep, slot, token_of, gate_vals, order)


def _local_combine(y, meta, t, d, dtype):
    keep, slot, token_of, gate_vals, order = meta
    e_cap = y.shape[0] * y.shape[1]
    y = y.reshape(e_cap, d)
    gathered = jnp.where(keep[:, None], y[slot], 0.0)
    w = gate_vals.reshape(-1)[order].astype(dtype)
    return jnp.zeros((t, d), dtype).at[token_of].add(gathered * w[:, None])


def _experts_swiglu(buf, w_gate, w_up, w_down):
    dt = buf.dtype
    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, w_gate.astype(dt)))
    u = jnp.einsum("ecd,edf->ecf", buf, w_up.astype(dt))
    return jnp.einsum("ecf,efd->ecd", g * u, w_down.astype(dt))


def moe_fwd_sharded(params, x, m: MoESpec, *, mesh: Mesh, dp, cp_axis,
                    tp_axis: str, overlap_k: int = 2):
    """x (B, S, D) sharded P(dp, cp_axis, None) -> same.

    Chooses "ep" when E % |tp| == 0 else "tp".  The ep-mode dispatch
    all-to-all runs through CROFT's K-chunked overlap stage.
    """
    b, s, d = x.shape
    tp = mesh.shape[tp_axis]
    e, k = m.n_experts, m.top_k
    # ep needs the expert dim to divide the axis AND sequence-sharded tokens
    # (decode segments are too small to shuffle); tp needs the ffn dim to
    # divide (true for every assigned config)
    mode = "ep" if (e % tp == 0 and cp_axis is not None) else "tp"
    if mode == "tp":
        assert m.d_ff_expert % tp == 0, (m.d_ff_expert, tp)

    # shard-local token count and capacity (identical statistics to the
    # global dispatch when tokens are iid-routed)
    cp = mesh.shape[cp_axis] if (cp_axis and mode == "ep") else 1
    dp_size = 1
    if dp is not None:
        dp_size = math.prod(
            mesh.shape[a] for a in (dp if isinstance(dp, tuple) else (dp,)))
    t_loc = (b // dp_size) * (s // cp)
    cap = max(8, -(-int(math.ceil(t_loc * k * m.capacity_factor / e)) // 8) * 8)

    x_spec = P(dp, cp_axis, None)
    fft_opts = FFTOptions(overlap_k=overlap_k)

    if mode == "ep":
        w_spec = P(tp_axis, None, None)           # experts sharded
        e_loc = e // tp

        def body(x_loc, router_w, w_gate, w_up, w_down):
            bb, ss, _ = x_loc.shape
            xt = x_loc.reshape(bb * ss, d)
            buf, meta = _local_dispatch(xt, router_w, m, cap)  # (E, C, D)
            # CROFT transpose: expert dim scattered out, capacity gathered
            # (E, C, D) -> (E/tp, C*tp, D); chunked for comm/compute overlap
            buf = _croft_stage(buf, comm_axis=tp_axis, split_axis=0,
                               concat_axis=1, chunk_axis=2, opts=fft_opts)
            y = _experts_swiglu(buf, w_gate, w_up, w_down)
            y = _croft_stage(y, comm_axis=tp_axis, split_axis=1,
                             concat_axis=0, chunk_axis=2, opts=fft_opts)
            out = _local_combine(y, meta, bb * ss, d, x_loc.dtype)
            return out.reshape(bb, ss, d)

        fn = shard_map(body, mesh=mesh,
                       in_specs=(x_spec, P(None, None), w_spec, w_spec,
                                 P(tp_axis, None, None)),
                       out_specs=x_spec)
        out = fn(x, params["router"], params["w_gate"], params["w_up"],
                 params["w_down"])
    else:
        # tokens replicated along tp (every shard must hold the SAME tokens
        # so the ffn-dim partial sums line up); sharded over dp only
        x_spec_tp = P(dp, None, None)
        w_spec = P(None, None, tp_axis)           # ffn dim sharded
        wd_spec = P(None, tp_axis, None)

        def body(x_loc, router_w, w_gate, w_up, w_down):
            bb, ss, _ = x_loc.shape
            xt = x_loc.reshape(bb * ss, d)
            buf, meta = _local_dispatch(xt, router_w, m, cap)
            buf = jax.lax.pcast(buf, (tp_axis,), to="varying")
            y = _experts_swiglu(buf, w_gate, w_up, w_down)
            # combine is linear in y: psum AFTER combining so the wire
            # carries (T, D) tokens, not the k*capacity-padded buffer
            out = _local_combine(y, meta, bb * ss, d, x_loc.dtype)
            out = jax.lax.psum(out, tp_axis)      # down-proj partial sums
            return out.reshape(bb, ss, d)

        fn = shard_map(body, mesh=mesh,
                       in_specs=(x_spec_tp, P(None, None), w_spec, w_spec,
                                 wd_spec),
                       out_specs=x_spec_tp)
        out = fn(x, params["router"], params["w_gate"], params["w_up"],
                 params["w_down"])

    if m.n_shared:
        out = out + ffn_fwd(params["shared"], x.reshape(-1, d),
                            "swiglu").reshape(b, s, d)
    return out
