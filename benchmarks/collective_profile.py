"""Figs 12-15 analogue: collective-op profile, CROFT pencil vs the
FFTW3-style pairwise-exchange transpose.

The paper's ITAC profile shows CROFT needs 64 MPI_Alltoall calls where
FFTW3 issues 864 MPI calls (112 Sendrecv) at P=8 / 1024^3.  Here we compile
both transpose strategies at P=8 on the CPU backend and count collective
ops in the lowered HLO — the same claim, measured on the compiled artifact.

Beyond the counts, each variant is wall-clocked and the three (ops,
bytes, wall) points are least-squares fit to ``wall = alpha*ops +
beta*bytes`` — a crude on-host calibration of the cost model's launch
latency (alpha) and inverse bandwidth (beta).  The estimates flow
through the ``repro.obs`` metrics registry (gauges
``collective_alpha_s`` / ``collective_beta_s_per_byte``) so cost-model
calibration and tracing share one output path; the CSV rows below read
them back out of the registry.

The fit is also persisted to a calibration JSON (``$CROFT_CALIBRATION``
when set, else ``calibration.json`` in the working directory) so *later*
processes can tune with measured constants:
``repro.tuning.cost_model.collective_constants`` loads the file via the
same env var, after checking the in-process registry.
"""

from __future__ import annotations

from benchmarks.common import emit, run_subprocess_bench

CODE = """
import time, jax, json
from repro.core import Croft3D, Decomposition, FFTOptions
from repro.launch import hlo_cost
from repro.launch.mesh import make_mesh
mesh = make_mesh((8,), ("p",))
N = {n}  # scaled-down stand-in for 1024^3 (same op structure)
out = {{}}
for tag, opts in {{
    "croft-alltoall": FFTOptions(overlap_k=2, transpose_impl="alltoall"),
    "croft-k1": FFTOptions(overlap_k=1, transpose_impl="alltoall"),
    "fftw3-pairwise": FFTOptions(overlap_k=1, transpose_impl="pairwise"),
}}.items():
    plan = Croft3D((N, N, N), mesh, Decomposition("slab", ("p",)), opts)
    cost = hlo_cost.analyze(plan.lower_forward().compile().as_text())
    out[tag] = {{k: v["count"] for k, v in cost.collectives.items()}}
    out[tag + "/bytes"] = sum(v["bytes"] for v in cost.collectives.values())
    x = jax.device_put(
        jax.numpy.zeros((N, N, N), "complex64"), plan.input_sharding)
    jax.block_until_ready(plan.forward(x))  # compile + warm
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(plan.forward(x))
        times.append(time.perf_counter() - t0)
    times.sort()
    out[tag + "/wall_s"] = times[len(times) // 2]
print(json.dumps(out))
"""

TAGS = ["croft-alltoall", "croft-k1", "fftw3-pairwise"]


def run(smoke: bool = False):
    import json

    import numpy as np

    from repro.obs import get_registry

    stdout = run_subprocess_bench(CODE.format(n=64 if smoke else 256),
                                  n_devices=8)
    data = json.loads(stdout.strip().splitlines()[-1])
    for tag in TAGS:
        counts = data[tag]
        total_ops = sum(counts.values())
        emit(f"fig12-15/{tag}/collective-ops", total_ops, True)
        emit(f"fig12-15/{tag}/collective-bytes", data[tag + "/bytes"], True)
        emit(f"fig12-15/{tag}/wall", data[tag + "/wall_s"] * 1e6, False)
    # the paper's headline ratio: pairwise needs ~(P-1)x more calls
    ratio = (sum(data["fftw3-pairwise"].values())
             / max(1, sum(data["croft-k1"].values())))
    emit("fig12-15/call-ratio-fftw3-over-croft", ratio, True)

    # alpha/beta calibration: wall ~= alpha*ops + beta*bytes over the
    # three variants, published through the shared metrics registry
    a = np.array([[sum(data[t].values()), data[t + "/bytes"]]
                  for t in TAGS], dtype=float)
    y = np.array([data[t + "/wall_s"] for t in TAGS])
    (alpha, beta), *_ = np.linalg.lstsq(a, y, rcond=None)
    reg = get_registry()
    reg.gauge("collective_alpha_s",
              "fitted per-collective launch seconds").set(alpha)
    reg.gauge("collective_beta_s_per_byte",
              "fitted seconds per collective byte").set(beta)
    emit("fig12-15/fit/alpha-us-per-collective",
         reg.gauge("collective_alpha_s").value * 1e6, True)
    emit("fig12-15/fit/beta-us-per-MiB",
         reg.gauge("collective_beta_s_per_byte").value * 1e6 * 2 ** 20, True)

    # persist the fit so other processes (CI tuning runs, training jobs)
    # can load it through $CROFT_CALIBRATION — the registry above only
    # calibrates *this* process
    import os

    from repro.tuning.cost_model import CALIBRATION_ENV
    path = os.environ.get(CALIBRATION_ENV) or "calibration.json"
    with open(path, "w") as f:
        json.dump({"collective_alpha_s": float(alpha),
                   "collective_beta_s_per_byte": float(beta),
                   "fit_points": len(TAGS)}, f, indent=2)
    emit("fig12-15/fit/saved", 1, True)
