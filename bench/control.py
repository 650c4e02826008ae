#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 bench/control.py --workload <cell> --seconds 2 \\
        --program-seeds 12 --control-seeds 3 [--first-seed N]

In one process: the cell's full run (set-up, a short window at the
cell's own load, the check) on ``--program-seeds`` seeds with the program
under test, then on ``--control-seeds`` seeds with the control in its
place: the plain reference at three bfloat16 passes, one precision step
below the float32 products at ``Precision.HIGHEST`` that the
configurations state.  Prints one JSON line per run, then a summary: for
each number compared, the largest reading of the program (the lower
reading) and the smallest of the control (the upper one).  The
benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import functools
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)


def readings(cell: dict, seeds, seconds: float, devices, factory=None,
             bm=None) -> list:
    """The numbers compared, one dict per seed."""
    from bench import harness

    out = []
    for seed in seeds:
        result = harness.run_cell(cell, seed=seed, seconds=seconds,
                                  trace=False, devices=devices,
                                  t_start=time.perf_counter(), bm=bm,
                                  system_factory=factory)
        numbers = {k: v["value"] for k, v in result["checks"].items()}
        out.append(numbers)
        print(json.dumps({"seed": seed, "control": factory is not None,
                          "steps": result["attempted"], "numbers": numbers}),
              flush=True)
        gc.collect()
    return out


def summary(program: list, control: list) -> dict:
    """Per number: the program's largest reading, the control's smallest,
    and their ratio."""
    table = {}
    for name in program[0]:
        lower = max(float(r[name]) for r in program)
        upper = min(float(r[name]) for r in control) if control else None
        table[name] = {"lower": lower, "upper": upper,
                       "ratio": (upper / lower if upper is not None and lower
                                 else None)}
    return table


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--program-seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=3_000_000_000)
    args = ap.parse_args(argv)

    import jax

    from bench import harness, system
    from repro.launch.compile_cache import use_compile_cache

    bm = harness.load_benchmark()
    cell = harness.find_cell(bm, args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"control: needs {cell['chips']} TPU chips, JAX found "
              f"{len(devices)} {devices[0].platform}", file=sys.stderr)
        return 2
    use_compile_cache()
    devices = devices[:cell["chips"]]
    first = args.first_seed
    program = readings(cell, range(first, first + args.program_seeds),
                       args.seconds, devices, bm=bm)
    control_seeds = range(first + 1000, first + 1000 + args.control_seeds)
    control = readings(cell, control_seeds, args.seconds, devices,
                       functools.partial(system.Reference, precision="high"),
                       bm=bm)
    print(json.dumps({"workload": args.workload,
                      "summary": summary(program, control)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
