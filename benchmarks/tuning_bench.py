"""Autotuner sweep: planner vs the hand-picked default plan.

For each benchmarked shape, runs the full planner pipeline on an
8-virtual-device CPU mesh in a subprocess (model ranking -> top-k
measurement -> wisdom), times the untuned default plan against the tuned
winner, and emits

  * ``tuning/<shape>/default`` and ``tuning/<shape>/tuned`` CSV rows
    (derived=0 — these are measured on this host), plus the modeled best
    (derived=1) for comparison, and
  * ``BENCH_tuning.json`` at the repo root: the ranked candidate report,
    measured times, chosen plan, and speedup per shape.

``run(smoke=True)`` is the CI entry point: one small shape, minimal
measure iterations.
"""

from __future__ import annotations

import json
import os

from benchmarks.common import REPO, emit, run_subprocess_bench

BENCH_JSON = os.path.join(REPO, "BENCH_tuning.json")

_SWEEP_CODE = """
import dataclasses, json, numpy as np, jax, jax.numpy as jnp
from repro.core import Croft3D
from repro import tuning
from repro.launch.mesh import make_mesh

shapes = {shapes!r}
top_k = {top_k}
iters = {iters}
mesh = make_mesh((2, 4), ("data", "model"))
report = {{"mesh": {{"data": 2, "model": 4}}, "backend": jax.default_backend(),
           "shapes": {{}}}}
for shape in shapes:
    shape = tuple(shape)
    result = tuning.tune(shape, mesh, mode="measure", top_k=top_k,
                         measure_iters=iters, wisdom_path={wisdom!r})
    # the planner already raced the untuned default candidate; read its
    # measurement from the report instead of recompiling it
    default = tuning.default_candidate(shape, dict(mesh.shape))
    t_default = None
    if default is not None:
        t_default = next((r.get("measured_s") for r in result.ranked
                          if r["label"] == default.label), None)
        if t_default is None:
            t_default = tuning.measure_candidate(shape, mesh, default,
                                                 warmup=2, iters=iters)
    tag = "x".join(map(str, shape))
    report["shapes"][tag] = {{
        "chosen": result.summary(),
        "decomp": {{"kind": result.decomp.kind,
                    "axes": [list(a) if isinstance(a, tuple) else a
                             for a in result.decomp.axes]}},
        "opts": dataclasses.asdict(result.opts),
        "model_s": result.model_s,
        "tuned_s": result.measured_s,
        "default_s": t_default,
        "speedup_vs_default": (t_default / result.measured_s
                               if result.measured_s and t_default else None),
        "ranked": result.ranked,
    }}
    if t_default is not None:
        print(f"ROW,tuning/{{tag}}/default,{{t_default * 1e6:.3f}},0")
    print(f"ROW,tuning/{{tag}}/tuned,{{result.measured_s * 1e6:.3f}},0")
    print(f"ROW,tuning/{{tag}}/modeled-best,{{result.model_s * 1e6:.3f}},1")
with open({out!r}, "w") as f:
    json.dump(report, f, indent=1, sort_keys=True)
print("JSON_WRITTEN")
"""


def run(smoke: bool = False) -> None:
    shapes = [(32, 32, 32)] if smoke else [(32, 32, 32), (64, 64, 64)]
    wisdom = os.path.join(REPO, "results", "wisdom.json")
    code = _SWEEP_CODE.format(shapes=[list(s) for s in shapes],
                              top_k=2 if smoke else 4,
                              iters=2 if smoke else 5,
                              wisdom=wisdom, out=BENCH_JSON)
    out = run_subprocess_bench(code, n_devices=8, timeout=1200)
    for line in out.splitlines():
        if line.startswith("ROW,"):
            _, name, us, derived = line.split(",")
            emit(name, float(us), bool(int(derived)))
    if "JSON_WRITTEN" not in out:
        raise RuntimeError("tuning sweep did not write BENCH_tuning.json")
