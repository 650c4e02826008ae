"""Serving driver for the transform service.

Drives :class:`repro.serve.TransformService` with a synthetic open-loop
request stream and prints latency / occupancy / plan-cache stats:

``python -m repro.launch.serve --shape 32,32,32 --problem mix
--requests 64 --qps 50 --wisdom wisdom.json``
"""

from __future__ import annotations

import argparse
import math
import time

import jax
import numpy as np

from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_mesh


def _mesh_for_transforms():
    """Pencil mesh over whatever devices exist; None = single device
    (the service then runs meshless local plans)."""
    n = len(jax.devices())
    if n < 2:
        return None
    py = int(math.sqrt(n))
    while n % py:
        py -= 1
    return make_mesh((py, n // py), ("y", "z"))


def transforms_main(args) -> None:
    from repro.serve import TransformService

    mesh = _mesh_for_transforms()
    shape = tuple(int(s) for s in args.shape.split(","))
    if len(shape) != 3:
        raise SystemExit(f"--shape must be 3-D, got {shape}")
    print(f"mesh: {dict(mesh.shape) if mesh else 'single-device'}  "
          f"shape: {shape}  problem: {args.problem}")

    rng = np.random.RandomState(args.seed)
    cplx = (rng.randn(*shape) + 1j * rng.randn(*shape)).astype(np.complex64)
    real = rng.randn(*shape).astype(np.float32)
    filt = rng.randn(*shape).astype(np.complex64)
    workload = {
        "c2c": [(cplx, {})],
        "r2c": [(real, {"problem": "r2c"})],
        "filtered": [(cplx, {"problem": "filtered", "h": filt})],
    }
    reqs = (workload["c2c"] * 3 + workload["r2c"] * 2
            + workload["filtered"]) if args.problem == "mix" \
        else workload[args.problem]

    svc = TransformService(
        mesh, max_batch=args.max_batch, max_wait_ms=args.max_wait_ms,
        wisdom_path=args.wisdom, measure_after=args.measure_after)
    with svc:
        t0 = time.monotonic()
        futs = []
        for i in range(args.requests):
            if args.qps > 0:
                delay = t0 + i / args.qps - time.monotonic()
                if delay > 0:
                    time.sleep(delay)
            x, kw = reqs[i % len(reqs)]
            futs.append(svc.submit(x, **kw))
        results = [f.result(timeout=600) for f in futs]
        bad = [r for r in results if not r.ok]
        if bad:
            raise SystemExit(f"{len(bad)} requests failed; first error: "
                             f"{bad[0].error}")
        stats = svc.stats()

    lat = stats["latency_ms"]
    print(f"served {stats['requests']} requests in "
          f"{stats['batches']} batches "
          f"(mean batch {stats['mean_batch']:.2f}, "
          f"occupancy {stats['occupancy']:.0%})")
    print(f"latency ms: p50={lat['p50']:.2f} p90={lat['p90']:.2f} "
          f"p99={lat['p99']:.2f}")
    cache = stats["plan_cache"]
    print(f"plan cache: {cache['stats']}  states: "
          f"{ {k.split('|')[0] + '|' + k.split('|')[-1]: v['state'] for k, v in cache['plans'].items()} }")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--shape", default="32,32,32",
                    help="3-D transform shape, e.g. 64,64,64")
    ap.add_argument("--problem", default="mix",
                    choices=("c2c", "r2c", "filtered", "mix"))
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--qps", type=float, default=0.0,
                    help="offered request rate; 0 = as fast as possible")
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--max-wait-ms", type=float, default=2.0)
    ap.add_argument("--wisdom", default=None,
                    help="wisdom file: cold starts read it, background "
                         "measure upgrades merge into it")
    ap.add_argument("--measure-after", type=int, default=None,
                    help="dispatches of a key before the background "
                         "measure-mode upgrade")
    args = ap.parse_args(argv)
    use_compile_cache()
    transforms_main(args)


if __name__ == "__main__":
    main()
