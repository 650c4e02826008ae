"""Benchmark helpers: timing, CSV rows, analytic cluster model.

Rows follow ``name,us_per_call,derived`` — ``derived=0`` means measured
wall time on this host; ``derived=1`` means modeled from roofline terms /
compiled artifacts (this container has no TPU to time).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from typing import Callable

import jax

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DRYRUN_DIR = os.path.join(REPO, "results", "dryrun")

ROWS: list[tuple[str, float, int]] = []


def emit(name: str, us_per_call: float, derived: bool):
    ROWS.append((name, us_per_call, int(derived)))
    print(f"{name},{us_per_call:.3f},{int(derived)}")


def time_fn(fn: Callable, *args, warmup: int = 1, iters: int = 3) -> float:
    """Median wall microseconds per call."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2] * 1e6


def load_dryrun(name: str):
    path = os.path.join(DRYRUN_DIR, name + ".json")
    if not os.path.exists(path):
        return None
    with open(path) as f:
        rec = json.load(f)
    return rec if rec.get("status") == "ok" else None


def run_subprocess_bench(code: str, n_devices: int, timeout: int = 600) -> str:
    """Run ``code`` in a child on ``n_devices`` virtual CPU devices: a
    CPU-mesh rehearsal, pinned to ``JAX_PLATFORMS=cpu`` so it never
    competes with the parent for an accelerator."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src") + os.pathsep + \
        env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=timeout)
    if proc.returncode != 0:
        raise RuntimeError(proc.stderr[-2000:])
    return proc.stdout


# --- analytic cluster model (paper tables without the cluster) -------------
# Param Bioblaze-analogue on TPU v5e constants; used to extrapolate the
# P-sweeps of tables 1-3 from the per-device transpose/compute volumes.

from repro.tuning.cost_model import (  # noqa: E402
    PRIOR_HBM_BW as HBM_BW, PRIOR_LINK_BW as LINK_BW,
    PRIOR_PEAK_FLOPS as PEAK_FLOPS)


def fft_step_model(grid, n_procs: int, decomposition: str = "pencil",
                   overlap: bool = True, layout: str = "natural",
                   itemsize: int = 8) -> dict:
    """Modeled 3-D FFT wall time on n_procs chips.

    compute: 5 N log2 N / P on the MXU;  memory: ~10 local passes;
    collective: transpose volume / link bw; overlap hides
    min(comm, compute+memory) when enabled (the paper's mechanism).
    """
    import math
    n_total = grid[0] * grid[1] * grid[2]
    local = n_total // n_procs * itemsize
    flops = 5 * n_total * sum(math.log2(g) for g in grid) / n_procs
    n_transposes = {"slab": 1, "pencil": 2, "cell": 3}[decomposition]
    if layout == "natural":
        n_transposes *= 2
    comm = n_transposes * local
    t_compute = flops / PEAK_FLOPS
    t_mem = 10 * local / HBM_BW
    t_comm = comm / LINK_BW
    if overlap:
        t = max(t_compute + t_mem, t_comm) + 0.1 * min(t_compute + t_mem, t_comm)
    else:
        t = t_compute + t_mem + t_comm
    return {"total_s": t, "compute_s": t_compute, "memory_s": t_mem,
            "collective_s": t_comm}
