"""Pseudo-spectral PDE driver on CROFT: periodic Poisson solve + a few
steps of 3-D viscous Burgers — the HPC workload class the paper targets
(turbulence codes built on distributed 3-D FFTs).

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/spectral_solver.py --devices 8

The fields are real, so the driver runs on the real-transform subsystem
(``repro.real`` via ``Croft3D(problem="r2c")``): the forward transform
returns the (N, N, N//2 + 1) Hermitian half spectrum and the inverse is
the exact c2r — with the packed two-for-one strategy, every pipeline
stage computes and communicates half of what the old
complex-embedding round trip paid.  ``--strategy embed`` switches back
to the embedding for comparison; the default lets the plan (or the
autotuner) pick.

The FFT plan comes from the autotuner (``repro.tuning``): ``--tune
measure`` (default) races the model-ranked top candidates on the mesh
— including the packed/embed strategy axis — ``--tune model`` picks
analytically with zero execution, and ``--tune wisdom`` reuses a plan
stored by a previous run (``--wisdom PATH``).

The Poisson solve runs the *fused spectral epilogue*: ``poisson_solve``
attaches the 1/(-k²) multiply to the forward transform's schedule
(``Croft3D.forward_filtered`` -> ``Schedule.with_epilogue`` /
``kernels/spectral_scale.py``), so the whole solve is one forward
dispatch plus one inverse — no separate pass over the spectrum
(``tests/test_real_fft.py`` holds its compiled bytes below forward plus
a separate multiply).
"""

import argparse
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import Croft3D, FFTOptions, poisson_solve
from repro.launch.mesh import make_mesh
from repro.launch.compile_cache import use_compile_cache


def wavenumbers(n):
    return jnp.fft.fftfreq(n, d=1.0 / n)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=64)
    ap.add_argument("--devices", type=int, default=1)
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--nu", type=float, default=0.05)
    ap.add_argument("--tune", default="measure",
                    choices=["model", "measure", "wisdom"],
                    help="autotuner mode (repro.tuning)")
    ap.add_argument("--wisdom", default=None,
                    help="wisdom JSON path for --tune wisdom / persistence")
    ap.add_argument("--strategy", default=None,
                    choices=["packed", "embed"],
                    help="force the r2c strategy (default: planner/auto)")
    args = ap.parse_args()
    use_compile_cache()

    n = args.n
    nh = n // 2 + 1
    if args.devices > 1:
        mesh = make_mesh((2, args.devices // 2), ("y", "z"))
        if args.strategy is None:
            plan = Croft3D.tuned((n, n, n), mesh, mode=args.tune,
                                 problem="r2c", wisdom_path=args.wisdom)
            print("tuned plan:", plan.tune_result.summary())
        else:
            # forcing a strategy bypasses the planner: hand-picked
            # default pencil plan (say so — --tune/--wisdom are ignored)
            print(f"--strategy {args.strategy}: bypassing the autotuner "
                  "(--tune/--wisdom ignored), using the default pencil plan")
            from repro.core import Decomposition
            plan = Croft3D((n, n, n), mesh,
                           Decomposition("pencil", ("y", "z")), FFTOptions(),
                           problem="r2c", strategy=args.strategy)
    else:
        mesh = None
        plan = Croft3D((n, n, n), None, None, FFTOptions(),
                       problem="r2c", strategy=args.strategy)
    print(f"r2c strategy: {plan.strategy} "
          f"(spectrum {plan.spectrum_shape}, input {plan.input_dtype})")

    # --- Poisson: manufactured solution ------------------------------------
    g = 2 * math.pi * np.arange(n) / n
    X, Y, Z = np.meshgrid(g, g, g, indexing="ij")
    u_true = np.sin(X) * np.cos(2 * Y) * np.sin(3 * Z)
    f = -(1 + 4 + 9) * u_true
    fd = jnp.asarray(f, jnp.float32)
    if mesh is not None:
        fd = jax.device_put(fd, plan.input_sharding)
    u = poisson_solve(fd, plan)
    err = float(jnp.max(jnp.abs(u - u_true)))
    print(f"Poisson {n}^3: max error {err:.2e}")

    # --- viscous Burgers (scalar, semi-implicit spectral stepping) ---------
    # the r2c spectrum halves kz: rfftfreq bins, all arrays (n, n, nh)
    kx = wavenumbers(n)[:, None, None]
    ky = wavenumbers(n)[None, :, None]
    kz = jnp.fft.rfftfreq(n, d=1.0 / n)[None, None, :]
    k2 = kx ** 2 + ky ** 2 + kz ** 2
    if mesh is not None:
        k2 = jax.device_put(k2, plan.output_sharding)
        kxs = jax.device_put(jnp.broadcast_to(kx, (n, n, nh)),
                             plan.output_sharding)
    else:
        kxs = jnp.broadcast_to(kx, (n, n, nh))

    u = jnp.asarray(np.sin(X) * np.cos(Y) * np.cos(Z), jnp.float32)
    if mesh is not None:
        u = jax.device_put(u, plan.input_sharding)
    dt = 0.01

    @jax.jit
    def step(u):
        u_hat = plan.forward(u)                  # real -> half spectrum
        ux = plan.inverse(1j * kxs.astype(plan.dtype) * u_hat)
        rhs = -u * ux                            # nonlinear term, real space
        rhs_hat = plan.forward(rhs)
        u_hat_new = (u_hat + dt * rhs_hat) / (1 + dt * args.nu * k2)
        return plan.inverse(u_hat_new)           # exact c2r: real output

    e0 = float(jnp.mean(u ** 2))
    t0 = time.perf_counter()
    for i in range(args.steps):
        u = step(u)
    jax.block_until_ready(u)
    dt_wall = (time.perf_counter() - t0) / args.steps
    e1 = float(jnp.mean(u ** 2))
    print(f"Burgers {args.steps} steps: energy {e0:.4f} -> {e1:.4f} "
          f"(viscous decay expected), {dt_wall * 1e3:.1f} ms/step")
    assert e1 < e0, "viscosity must dissipate energy"
    print("OK")


if __name__ == "__main__":
    main()
