"""Training through the transform: a learned spectral filter, end to end.

A real-space gate and a k-space filter around the distributed r2c
transform (``repro.grad.filter``), trained with SGD.  Gradients replay
the plan's *adjoint schedule* (``repro.grad``), and with more than one
device the plan comes from ``Croft3D.tuned(..., grad=True)``: the
autotuner prices forward + adjoint, so the winning plan is optimal for
the training step, not just inference.

    PYTHONPATH=src XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        python examples/train_filter.py --steps 20
"""

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import Croft3D
from repro.grad.filter import (init_spectral_filter_params,
                               make_spectral_train_step,
                               place_spectral_filter_params,
                               spectral_filter_apply)
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_mesh


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--size", type=int, default=32,
                    help="grid size N (N^3 field)")
    ap.add_argument("--lr", type=float, default=0.05,
                    help="SGD learning rate")
    args = ap.parse_args()
    use_compile_cache()

    n = args.size
    shape = (n, n, n)
    n_dev = len(jax.devices())
    if n_dev == 1:
        plan = Croft3D(shape, problem="r2c")
        print(f"spectral filter: {shape} single-device")
    else:
        if n_dev % 2 == 0:
            mesh = make_mesh((n_dev // 2, 2), ("y", "x"))
        else:
            mesh = make_mesh((n_dev,), ("y",))
        # grad=True: the planner prices forward + adjoint schedule, so
        # the chosen plan is the best *training step*, not best forward
        plan = Croft3D.tuned(shape, mesh, mode="model", problem="r2c",
                             grad=True)
        print(f"spectral filter: {shape} on {dict(mesh.shape)} — "
              f"{plan.tune_result.summary()}")

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(*shape), plan.input_dtype)
    if plan.mesh is not None:
        x = jax.device_put(x, plan.input_sharding)
    true = place_spectral_filter_params(plan, {
        "gate": jnp.asarray(1.0 + 0.3 * rng.randn(*shape), jnp.float32),
        "filter": jnp.asarray(
            1.0 + 0.3 * rng.randn(*plan.spectrum_shape), jnp.float32)})
    target = spectral_filter_apply(plan, true, x)
    step, _ = make_spectral_train_step(plan, lr=args.lr)
    params = place_spectral_filter_params(
        plan, init_spectral_filter_params(jax.random.PRNGKey(1), plan))
    for i in range(args.steps):
        params, loss = step(params, x, target)
        if i % max(1, args.steps // 10) == 0 or i == args.steps - 1:
            print(f"step {i:4d}  loss {float(loss):.4f}")


if __name__ == "__main__":
    main()
