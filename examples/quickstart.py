"""CROFT quickstart: plan, transform, verify — single device or any mesh.

    PYTHONPATH=src python examples/quickstart.py
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/quickstart.py --devices 8
"""

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import Croft3D, Decomposition, FFTOptions
from repro.launch.mesh import make_mesh
from repro.launch.compile_cache import use_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=64)
    ap.add_argument("--devices", type=int, default=1)
    ap.add_argument("--k", type=int, default=2, help="CROFT overlap chunks")
    ap.add_argument("--decomp", default="pencil",
                    choices=["pencil", "slab", "cell"])
    args = ap.parse_args()
    use_compile_cache()

    n = args.n
    rng = np.random.RandomState(0)
    x = (rng.randn(n, n, n) + 1j * rng.randn(n, n, n)).astype(np.complex64)

    if args.devices > 1:
        if args.decomp == "pencil":
            py = 2
            mesh = make_mesh((py, args.devices // py), ("y", "z"))
            decomp = Decomposition("pencil", ("y", "z"))
        elif args.decomp == "slab":
            mesh = make_mesh((args.devices,), ("z",))
            decomp = Decomposition("slab", ("z",))
        else:
            mesh = make_mesh((2, 2, args.devices // 4), ("a", "b", "c"))
            decomp = Decomposition("cell", ("a", "b", "c"))
    else:
        mesh = decomp = None

    opts = FFTOptions(overlap_k=args.k)
    plan = Croft3D((n, n, n), mesh, decomp, opts)
    print(f"grid {n}^3, decomposition={args.decomp}, K={args.k}, "
          f"devices={args.devices}")
    if mesh is not None:
        print(f"local pencil shape per device: {plan.local_shape()}")

    xd = jnp.asarray(x)
    if mesh is not None:
        xd = jax.device_put(xd, plan.input_sharding)
    y = plan.forward(xd)
    ref = np.fft.fftn(x)
    err = float(jnp.max(jnp.abs(y - ref))) / np.abs(ref).max()
    print(f"forward vs numpy.fftn relative error: {err:.2e}")

    xb = plan.inverse(y)
    rerr = float(jnp.max(jnp.abs(xb - x)))
    print(f"inverse(forward(x)) max abs error:   {rerr:.2e}")
    print(f"analytic FLOPs: {plan.flops_model():.3e}, "
          f"comm bytes/chip: {plan.comm_bytes_model():.3e}")


if __name__ == "__main__":
    main()
