"""Benchmark harness: one module per paper table/figure.

``PYTHONPATH=src python -m benchmarks.run``  prints ``name,us_per_call,derived``
CSV rows (derived=0: measured on this host; 1: modeled from compiled
artifacts / roofline constants — no TPU in this container).

``--smoke`` runs only the fast sweeps — the autotuner
(``benchmarks.tuning_bench``), the real-transform packed-vs-embed
comparison (``benchmarks.rfft_bench``), the transpose overlap-engine
sweep (``benchmarks.overlap_bench``), the transform-service load
sweep (``benchmarks.serve_bench``), and the collective-op profile with
its alpha/beta calibration fit (``benchmarks.collective_profile``) —
the CI path exercising the planner, the r2c pipeline, all three
transpose impls, and the serving layer (including its deterministic
batched-collective gate) end to end on every push.

``--trace DIR`` has the serve sweep save its Chrome-trace JSON
(``DIR/serve_trace.json``) alongside its ``BENCH_serve.json`` phase
breakdown.
"""

import argparse
import sys
import traceback

FULL_MODULES = ["benchmarks.fft_tables", "benchmarks.collective_profile",
                "benchmarks.kernel_micro", "benchmarks.lm_roofline",
                "benchmarks.train_bench", "benchmarks.tuning_bench",
                "benchmarks.search_bench", "benchmarks.rfft_bench",
                "benchmarks.overlap_bench", "benchmarks.serve_bench",
                "benchmarks.chaos_bench"]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="fast tuner-only sweep (CI)")
    ap.add_argument("--trace", metavar="DIR", default=None,
                    help="save the serve sweep's Chrome-trace JSON "
                         "into DIR")
    args = ap.parse_args()

    print("name,us_per_call,derived")
    failures = []
    if args.smoke:
        import os

        from benchmarks import (chaos_bench, collective_profile,
                                overlap_bench, rfft_bench, serve_bench,
                                tuning_bench)
        tdir = args.trace
        if tdir:
            os.makedirs(tdir, exist_ok=True)
        tuning_bench.run(smoke=True)
        rfft_bench.run(smoke=True)
        overlap_bench.run(smoke=True)
        serve_bench.run(
            smoke=True,
            trace=os.path.join(tdir, "serve_trace.json") if tdir else None)
        chaos_bench.run(smoke=True)
        collective_profile.run(smoke=True)
        return
    for modname in FULL_MODULES:
        try:
            mod = __import__(modname, fromlist=["run"])
            mod.run()
        except Exception as e:
            failures.append((modname, e))
            print(f"# ERROR in {modname}: {type(e).__name__}: {e}",
                  file=sys.stderr)
            traceback.print_exc()
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
