"""GPipe-style pipeline parallelism over the ``pod`` axis.

The production meshes run the pod axis as pure data parallelism (DESIGN.md
§4); at >2 pods or when per-pod memory is the binding constraint, pipeline
staging is the alternative.  This module provides the schedule as a
self-contained, tested substrate component:

  * stage p holds layers [p·L/P, (p+1)·L/P) — params sharded over ``pod``
    on the stacked layer axis;
  * microbatches flow through a ``shard_map`` ppermute ring with the GPipe
    schedule: step t processes microbatch (t - stage) at each stage, so a
    P-stage pipeline with M microbatches takes M + P - 1 steps
    (bubble fraction (P-1)/(M+P-1));
  * autodiff flows through ``ppermute`` natively, so ``jax.grad`` of the
    pipelined forward is the pipelined backward.

``pipeline_apply`` is deliberately model-agnostic: it pipelines any
``layer_fn(params_slice, x) -> x`` whose stacked params divide across
stages.  Equivalence to sequential execution is asserted in
``tests/test_pipeline.py``.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def bubble_fraction(n_stages: int, n_micro: int) -> float:
    return (n_stages - 1) / (n_micro + n_stages - 1)


def pipeline_apply(layer_fn: Callable, stacked_params, x, *, mesh: Mesh,
                   stage_axis: str, n_micro: int):
    """Run ``x`` through all stacked layers, pipelined over ``stage_axis``.

    layer_fn(params_t, h) -> h applies ONE layer.
    stacked_params: pytree with leading layer axis L (L % n_stages == 0),
    sharded (or shardable) over ``stage_axis``.
    x: (B, ...) global batch; B % n_micro == 0.
    """
    n_stages = mesh.shape[stage_axis]
    lead = jax.tree.leaves(stacked_params)[0].shape[0]
    assert lead % n_stages == 0, (lead, n_stages)
    b = x.shape[0]
    assert b % n_micro == 0, (b, n_micro)
    mb = b // n_micro

    p_spec = jax.tree.map(lambda _: P(stage_axis), stacked_params)
    x_spec = P(*([None] * x.ndim))

    def body(params_loc, x_all):
        # params_loc: (L/P, ...) this stage's layers; x_all replicated
        stage = jax.lax.axis_index(stage_axis)
        x_all = jax.lax.pcast(x_all, (stage_axis,), to="varying")
        micro = x_all.reshape((n_micro, mb) + x_all.shape[1:])

        def run_stage(h):
            def one(carry, p_t):
                return layer_fn(p_t, carry), None
            h, _ = jax.lax.scan(one, h, params_loc)
            return h

        n_steps = n_micro + n_stages - 1
        outputs = jnp.zeros_like(micro)
        buf = jax.lax.pcast(
            jnp.zeros((mb,) + x_all.shape[1:], x_all.dtype),
            (stage_axis,), to="varying")
        fwd_perm = [(i, i + 1) for i in range(n_stages - 1)]

        def step(t, carry):
            buf, outputs = carry
            # stage 0 injects microbatch t; others take the ppermuted input
            inject = jax.lax.dynamic_slice_in_dim(
                micro, jnp.clip(t, 0, n_micro - 1), 1, 0)[0]
            h_in = jnp.where(stage == 0, inject, buf)
            h_out = run_stage(h_in)
            # last stage commits microbatch (t - (P-1)) when valid
            out_idx = t - (n_stages - 1)
            commit = (stage == n_stages - 1) & (out_idx >= 0)
            upd = jax.lax.dynamic_update_slice_in_dim(
                outputs, h_out[None], jnp.maximum(out_idx, 0), 0)
            outputs = jnp.where(commit, upd, outputs)
            buf = jax.lax.ppermute(h_out, stage_axis, fwd_perm)
            return buf, outputs

        buf, outputs = jax.lax.fori_loop(0, n_steps, step, (buf, outputs))
        # result lives on the last stage; broadcast it (psum of masked)
        outputs = jax.lax.psum(
            jnp.where(stage == n_stages - 1, outputs,
                      jnp.zeros_like(outputs)), stage_axis)
        return outputs.reshape(x_all.shape)

    fn = shard_map(body, mesh=mesh, in_specs=(p_spec, x_spec),
                   out_specs=x_spec)
    return fn(stacked_params, x)
