"""Transform-service quickstart: submit heterogeneous spectral transforms
to one shared, plan-cached, continuously batched service.

Three client "apps" share the service concurrently — a c2c solver, an
r2c analysis pass, and a filtered (Poisson-style) solve.  Requests that
land in the same dispatch window and hit the same compiled executable
are stacked into one batch, which costs the SAME number of collectives
as a single request (the PR 5 property the bench gates).

    PYTHONPATH=src python examples/serve_transforms.py
    PYTHONPATH=src python examples/serve_transforms.py --wisdom wisdom.json

Run it twice with ``--wisdom``: the second run starts warm from the
plans the first run's background measurement merged into the file.
"""

import argparse
import threading

import numpy as np

from repro.serve import TransformService
from repro.launch.compile_cache import use_compile_cache

N = 16


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--wisdom", default=None,
                    help="wisdom file for cross-run plan reuse")
    ap.add_argument("--requests", type=int, default=8,
                    help="requests per client app")
    args = ap.parse_args()
    use_compile_cache()

    rng = np.random.RandomState(0)
    errs = []

    def solver(svc):
        """c2c round trip: forward, then inverse of the spectrum."""
        x = (rng.randn(N, N, N) + 1j * rng.randn(N, N, N)
             ).astype(np.complex64)
        for _ in range(args.requests):
            y = svc.transform(x, problem="c2c")
            x_back = svc.transform(y, problem="c2c", direction="inverse")
            errs.append(("c2c roundtrip",
                         float(np.max(np.abs(x_back - x)))))

    def analysis(svc):
        """r2c half-spectrum of a real field (inverse needs shape=)."""
        x = rng.randn(N, N, N).astype(np.float32)
        for _ in range(args.requests):
            y = svc.transform(x, problem="r2c")
            x_back = svc.transform(y, problem="r2c", direction="inverse",
                                   shape=(N, N, N))
            errs.append(("r2c roundtrip",
                         float(np.max(np.abs(x_back - x)))))

    def filtered(svc):
        """Fused forward+filter epilogue: FFT(x) * h in one dispatch."""
        x = (rng.randn(N, N, N) + 1j * rng.randn(N, N, N)
             ).astype(np.complex64)
        h = np.exp(-0.1 * np.arange(N * N * N).reshape(N, N, N)
                   ).astype(np.complex64)
        for _ in range(args.requests):
            y = svc.transform(x, problem="filtered", h=h)
            ref = svc.transform(x, problem="c2c") * h
            errs.append(("filtered vs c2c*h",
                         float(np.max(np.abs(y - ref)))))

    with TransformService(max_batch=4, max_wait_ms=2.0,
                          wisdom_path=args.wisdom) as svc:
        threads = [threading.Thread(target=fn, args=(svc,))
                   for fn in (solver, analysis, filtered)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        stats = svc.stats()

    worst = {}
    for name, err in errs:
        worst[name] = max(worst.get(name, 0.0), err)
    for name, err in sorted(worst.items()):
        print(f"{name:20s} max|err| = {err:.3e}")
    print(f"\nserved {stats['requests']} requests in {stats['batches']} "
          f"batches (mean batch {stats['mean_batch']:.2f}, occupancy "
          f"{stats['occupancy']:.0%})")
    print(f"plan cache: {stats['plan_cache']['stats']}")
    assert all(e < 1e-3 for e in worst.values()), worst
    print("OK")


if __name__ == "__main__":
    main()
