"""Learned spectral filter: training through the transform.

A two-parameter spectral layer over a 3-D field,

    y_hat(theta; x) = F( gate . x ) . filter

with a learnable real-space gate (full grid) and a learnable k-space
filter (half spectrum for r2c plans, full for c2c).  The transform is a
planned ``Croft3D``: the k-space multiply runs as the plan's fused
epilogue (``forward_filtered``) and gradients replay the plan's adjoint
schedule (:mod:`repro.grad.vjp`) instead of XLA differentiating through
``shard_map`` collectives.  This is the workload ``Croft3D.tuned(...,
grad=True)`` plans for; ``tests/test_grad.py`` holds its training,
gradient and backward-collective gates.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


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


def spectral_loss_fn(plan, params, x, target):
    """Normalized spectral MSE of the filter layer against a target
    half/full spectrum.

    Normalizing by N^3 undoes the unnormalized forward transform's
    energy blow-up (Parseval), so per-mode curvature w.r.t. the filter
    is O(1) and plain SGD converges with an O(0.1) learning rate.
    """
    pred = spectral_filter_apply(plan, params, x)
    d = pred - target
    n3 = float(plan.shape[0] * plan.shape[1] * plan.shape[2])
    return jnp.sum(jnp.real(d * jnp.conj(d))) / n3


def make_spectral_train_step(plan, lr: float = 0.05):
    """SGD step for the learned spectral filter over a planned transform.

    Returns ``(step, loss_fn)``: ``step(params, x, target) -> (params,
    loss)`` is jitted; ``loss_fn(params, x, target)`` is the raw scalar
    loss (what the gradient oracles differentiate).
    """

    def loss_fn(params, x, target):
        return spectral_loss_fn(plan, params, x, target)

    def step(params, x, target):
        loss, grads = jax.value_and_grad(loss_fn)(params, x, target)
        new = jax.tree.map(lambda p, g: p - lr * g, params, grads)
        return new, loss

    return jax.jit(step), loss_fn
