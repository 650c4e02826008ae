#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Sets up the cell named in ``BENCHMARK.json`` (builds the plan, loads or
compiles its programs through the persistent compilation cache at
``<checkout>/.jax_cache``, makes the inputs from the seed on the device,
warms up the cell's own shapes), measures for ``--seconds``, checks what
the timed path produced against the plain reference, and prints one JSON
line last on standard output.  ``--trace 1`` runs the window under the
JAX profiler and reports the per-layer metrics instead of the end-to-end
ones.  A backend that is not a TPU, or fewer chips than the cell asks
for, ends the run with exit code 2 and no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HOST_CORES = 4
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    from bench import harness

    bm = harness.load_benchmark()
    cell = harness.find_cell(bm, args.workload)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: JAX found no TPU (platform {devices[0].platform!r}); "
              "nothing was run", file=sys.stderr)
        return 2
    if len(devices) < cell["chips"]:
        print(f"bench: {args.workload} needs {cell['chips']} chips, JAX "
              f"found {len(devices)}", file=sys.stderr)
        return 2

    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    result = harness.run_cell(cell, seed=args.seed, seconds=args.seconds,
                              trace=bool(args.trace),
                              devices=devices[:cell["chips"]],
                              t_start=T_START, bm=bm)
    sys.stdout.flush()
    for line in harness.format_checks(result["checks"]):
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result))
    return 0


def pin_host_cores(n: int = HOST_CORES) -> None:
    """Keep this process, and the threads JAX starts later, on the same
    ``n`` cores: a cell of short steps is bound by its host loop, and a
    loop that the scheduler moves between cores spreads from run to run."""
    if hasattr(os, "sched_setaffinity"):
        os.sched_setaffinity(0, sorted(os.sched_getaffinity(0))[:n])


if __name__ == "__main__":
    pin_host_cores()
    sys.exit(main())
