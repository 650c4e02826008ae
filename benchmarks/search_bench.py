"""Schedule-search benchmark: does searching *pipelines* beat searching
knobs?  Writes ``BENCH_search.json`` with two deterministic gates:

* **model_win** (gate A): at the anisotropic gate point —
  ``(512, 512, 4)`` on a 2x4 pencil mesh, where the first transpose's
  chunk axis is down to one plane per rank and cannot split — the best
  *searched* schedule's modeled cost must be strictly below the best
  fixed-builder plan's, with BOTH priced by the same per-stage
  compute/collective combine (fixed candidates are wrapped via
  ``ScheduleCandidate.from_candidate``; the legacy whole-plan combine
  would average the unhideable stage away — per-stage attribution from
  ``repro.obs`` is precisely what showed it shouldn't be).  The winner
  must also be fixed-inexpressible (``as_options_candidate() is None``),
  i.e. a genuinely new point: mixed per-stage impls/K or a transpose
  order no builder emits.

* **hlo_mirror** (gate B): the winning pipeline structure, compiled at
  ``(32, 32, 4)`` on an 8-virtual-device CPU mesh, must contain exactly
  the per-stage predicted collective ops (``cost_model.
  predicted_collectives``): ring stages K_eff*(P-1) collective-permutes,
  alltoall stages K_eff all-to-alls.  This pins the per-stage override
  threading through the executor — an override silently ignored would
  compile to the homogeneous counts and fail here.

Wall-clock of searched-vs-fixed at the compile point is recorded
(``measured``) but NOT gated: on a single-host virtual mesh the
collectives are memcpys, so the modeled contention regime does not
reproduce — the numbers are for eyeballing, the model and HLO structure
are the contract.

``python -m benchmarks.search_bench --smoke`` is the CI entry point
(both gates; full mode adds a second mesh split and grad-problem rows).
"""

from __future__ import annotations

import argparse
import json
import os

import jax.numpy as jnp

from benchmarks.common import REPO, emit, run_subprocess_bench

BENCH_JSON = os.path.join(REPO, "BENCH_search.json")

GATE_SHAPE = (512, 512, 4)
GATE_AXES = {"data": 2, "model": 4}
COMPILE_SHAPE = (32, 32, 4)


def _gate_model_win(shape, axes) -> dict:
    """Gate A at one (shape, mesh) point; returns the report section."""
    from repro.tuning import candidates as cand_lib
    from repro.tuning import cost_model

    fixed = cand_lib.enumerate_candidates(shape, axes)
    wrapped, skipped = [], 0
    for c in fixed:
        try:
            wrapped.append(cand_lib.ScheduleCandidate.from_candidate(c))
        except ValueError:
            skipped += 1  # cell pipelines carry packing ops; logged below
    searched = cand_lib.enumerate_schedule_candidates(shape, axes)
    if skipped:
        print(f"# note: {skipped} fixed candidates (cell regroup "
              "pipelines) not priceable per-stage; compared on the rest")
    rw = cost_model.rank_candidates(shape, wrapped, axes, jnp.complex64, 1)
    rs = cost_model.rank_candidates(shape, searched, axes, jnp.complex64, 1)
    best_fixed, c_fixed = rw[0]
    best_sched, c_sched = rs[0]
    section = {
        "shape": list(shape),
        "axes": dict(axes),
        "n_fixed": len(wrapped),
        "n_fixed_unpriceable": skipped,
        "n_searched": len(searched),
        "best_fixed": {"plan_key": best_fixed.plan_key,
                       "model_s": c_fixed.total_s},
        "best_searched": {"plan_key": best_sched.plan_key,
                          "stages": best_sched.stage_summary(),
                          "model_s": c_sched.total_s},
        "win": c_sched.total_s < c_fixed.total_s,
        "inexpressible": best_sched.as_options_candidate() is None,
    }
    emit(f"search/model-fixed/{'x'.join(map(str, shape))}",
         c_fixed.total_s * 1e6, True)
    emit(f"search/model-searched/{'x'.join(map(str, shape))}",
         c_sched.total_s * 1e6, True)
    return section


_HLO_CODE = """
import json, numpy as np, jax, jax.numpy as jnp
from repro.core import Croft3D
from repro.launch import hlo_cost
from repro.tuning import candidates as cand_lib, cost_model
from repro.tuning.measure import _random_input, time_forward
from repro.launch.mesh import make_mesh

shape = tuple({shape})
axes = {axes}
mesh = make_mesh(tuple(axes.values()), tuple(axes))

cand = cand_lib.ScheduleCandidate.from_plan_key({token!r})
cand.validate(shape, axes)
sched = cand.build_schedule()
pred = cost_model.predicted_collectives(sched, shape, axes, cand.opts)

plan = Croft3D(shape, mesh=mesh, schedule=cand)
cost = hlo_cost.analyze(plan.lower_forward().compile().as_text())
got = {{k: int(v["count"]) for k, v in cost.collectives.items()}}
got = {{k: v for k, v in got.items() if v}}
pred = {{k: v for k, v in pred.items() if v}}

# wall clock, searched vs the untuned fixed default (recorded, NOT gated)
t_sched = time_forward(plan, warmup=2, iters=5)
dflt = cand_lib.default_candidate(shape, axes)
pf = Croft3D(shape, mesh, dflt.decomp, dflt.opts)
t_fixed = time_forward(pf, warmup=2, iters=5)

print("SEARCHJSON " + json.dumps({{
    "predicted": pred, "compiled": got, "match": pred == got,
    "measured_searched_s": t_sched, "measured_fixed_s": t_fixed}}))
"""


def _gate_hlo_mirror(token: str, shape, axes) -> dict:
    out = run_subprocess_bench(
        _HLO_CODE.format(shape=list(shape), axes=dict(axes), token=token),
        n_devices=8, timeout=900)
    for line in out.splitlines():
        if line.startswith("SEARCHJSON "):
            section = json.loads(line[len("SEARCHJSON "):])
            break
    else:
        raise RuntimeError("hlo-mirror subprocess produced no report")
    section.update(shape=list(shape), axes=dict(axes), plan_key=token)
    emit(f"search/wall-searched/{'x'.join(map(str, shape))}",
         section["measured_searched_s"] * 1e6, False)
    emit(f"search/wall-fixed/{'x'.join(map(str, shape))}",
         section["measured_fixed_s"] * 1e6, False)
    return section


def run(smoke: bool = False) -> None:
    report = {"model_win": [], "hlo_mirror": []}

    points = [(GATE_SHAPE, GATE_AXES)]
    if not smoke:
        points.append((GATE_SHAPE, {"data": 4, "model": 2}))
    for shape, axes in points:
        report["model_win"].append(_gate_model_win(shape, axes))

    gate_a = report["model_win"][0]
    if not (gate_a["win"] and gate_a["inexpressible"]):
        _dump(report)
        raise SystemExit(
            "REGRESSION: schedule search no longer finds a fixed-"
            f"inexpressible win at the gate point: {gate_a}")

    # gate B compiles the winning pipeline structure at the small shape
    # (same decomp/opts/stage tokens; the win shape's z extent carries
    # over so the chunk-indivisibility regime is preserved)
    token = gate_a["best_searched"]["plan_key"]
    report["hlo_mirror"].append(
        _gate_hlo_mirror(token, COMPILE_SHAPE, GATE_AXES))
    if not report["hlo_mirror"][0]["match"]:
        _dump(report)
        raise SystemExit(
            "REGRESSION: compiled collective counts diverge from the "
            f"per-stage prediction: {report['hlo_mirror'][0]}")

    _dump(report)


def _dump(report: dict) -> None:
    with open(BENCH_JSON, "w") as f:
        json.dump(report, f, indent=1, sort_keys=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="fast CI run: one gate point per gate")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    run(smoke=args.smoke)


if __name__ == "__main__":
    main()
