"""LASP-style sequence-parallel linear recurrences.

When the sequence axis is sharded over the ``model`` mesh axis (context
parallelism), a linear recurrence needs its state threaded across shards.
Both RG-LRU (vector state) and RWKV-6 (matrix state) updates are affine
maps, so shard composition is associative and the cross-shard prefix is a
log-depth Hillis-Steele scan over ``ppermute`` steps (4 hops on a 16-way
axis) — the distributed analogue of the chunked scans in
``models/recurrent.py``, and the sequence-domain cousin of CROFT's
transpose pipeline (DESIGN.md §4).

Each wrapper: one local pass (state starting from zero), a log-depth
exclusive prefix of (total_decay, contribution) across shards, then a cheap
local correction term — no second full pass.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.models import recurrent as rec


def _prefix_scan(pairs_combine: Callable, identity, local, axis_name: str):
    """Hillis-Steele inclusive scan over the mesh axis, then shift by one
    rank to make it exclusive (rank 0 receives ``identity``)."""
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    acc = local
    d = 1
    while d < n:
        perm = [(i, i + d) for i in range(n - d)]
        incoming = jax.tree.map(
            lambda x: jax.lax.ppermute(x, axis_name, perm), acc)
        combined = pairs_combine(incoming, acc)   # incoming applied first
        acc = jax.tree.map(
            lambda c, a: jnp.where(idx >= d, c, a), combined, acc)
        d *= 2
    # shift: rank i gets rank i-1's inclusive value
    perm1 = [(i, i + 1) for i in range(n - 1)]
    shifted = jax.tree.map(lambda x: jax.lax.ppermute(x, axis_name, perm1), acc)
    return jax.tree.map(
        lambda s, ident: jnp.where(idx == 0, ident, s), shifted, identity)


def cp_vector_recurrence(log_a, b, h0, *, mesh: Mesh, cp_axis: str,
                         batch_spec, chunk: int = 256):
    """Distributed ``rec.vector_recurrence``: (B, T, D) with T sharded over
    ``cp_axis``.  h0 (B, D) replicated along cp_axis."""

    spec_t = P(batch_spec, cp_axis, None)
    spec_b = P(batch_spec, None)

    def body(la_loc, b_loc, h0_loc):
        # replicated operands must be marked varying before mixing with
        # shard-local values inside scans (shard_map vma typing)
        h0_loc = jax.lax.pcast(h0_loc, (cp_axis,), to="varying")
        # local pass from zero state
        h_loc, h_last = rec.vector_recurrence(
            la_loc, b_loc, jnp.zeros_like(h0_loc), chunk)
        l_tot = jnp.sum(la_loc, axis=1)                     # (B, D)

        def combine(first, second):
            lf, cf = first
            ls, cs = second
            return lf + ls, jnp.exp(ls) * cf + cs

        ident = (jnp.zeros_like(l_tot), jnp.zeros_like(h_last))
        l_ex, c_ex = _prefix_scan(combine, ident, (l_tot, h_last), cp_axis)
        h_in = jnp.exp(l_ex) * h0_loc + c_ex                # state entering shard
        # correction: h_t += exp(cum log_a through t) * h_in
        a_cum = jnp.cumsum(la_loc, axis=1)
        h = h_loc + jnp.exp(a_cum) * h_in[:, None, :]
        # global final state lives on the last rank; broadcast via psum
        idx = jax.lax.axis_index(cp_axis)
        n = jax.lax.axis_size(cp_axis)
        h_out_last = jax.lax.psum(
            jnp.where(idx == n - 1, h[:, -1], jnp.zeros_like(h[:, -1])),
            cp_axis)
        return h, h_out_last

    fn = shard_map(body, mesh=mesh,
                   in_specs=(spec_t, spec_t, spec_b),
                   out_specs=(spec_t, spec_b))
    return fn(log_a, b, h0)


def cp_matrix_recurrence(log_w, k, v, r, u, s0, *, mesh: Mesh, cp_axis: str,
                         batch_spec, chunk: int = 64):
    """Distributed ``rec.matrix_recurrence``: (B, T, H, *) with T sharded
    over ``cp_axis``; s0 (B, H, K, V) replicated along it."""

    spec_t = P(batch_spec, cp_axis, None, None)
    spec_s = P(batch_spec, None, None, None)
    spec_u = P(None, None)

    def body(lw_loc, k_loc, v_loc, r_loc, u_loc, s0_loc):
        s0_loc = jax.lax.pcast(s0_loc, (cp_axis,), to="varying")
        u_loc = jax.lax.pcast(u_loc, (cp_axis,), to="varying")
        o_loc, s_loc = rec.matrix_recurrence(
            lw_loc, k_loc, v_loc, r_loc, u_loc,
            jnp.zeros_like(s0_loc), chunk)
        l_tot = jnp.sum(lw_loc, axis=1)                     # (B, H, K)

        def combine(first, second):
            lf, cf = first
            ls, cs = second
            return lf + ls, jnp.exp(ls)[..., None] * cf + cs

        ident = (jnp.zeros_like(l_tot), jnp.zeros_like(s_loc))
        l_ex, c_ex = _prefix_scan(combine, ident, (l_tot, s_loc), cp_axis)
        s_in = jnp.exp(l_ex)[..., None] * s0_loc + c_ex
        # correction: o_t += (r_t ⊙ exp(cum log_w through t-1)) · s_in
        dcum = jnp.cumsum(lw_loc, axis=1)
        d_prev = dcum - lw_loc
        o = o_loc + jnp.einsum("bthk,bhkv->bthv",
                               r_loc * jnp.exp(d_prev), s_in)
        d_last = dcum[:, -1]
        s_out = jnp.exp(d_last)[..., None] * s_in + s_loc
        idx = jax.lax.axis_index(cp_axis)
        n = jax.lax.axis_size(cp_axis)
        s_out = jax.lax.psum(
            jnp.where(idx == n - 1, s_out, jnp.zeros_like(s_out)), cp_axis)
        return o, s_out

    fn = shard_map(body, mesh=mesh,
                   in_specs=(spec_t, spec_t, spec_t, spec_t, spec_u, spec_s),
                   out_specs=(spec_t, spec_s))
    return fn(log_w, k, v, r, u, s0)
