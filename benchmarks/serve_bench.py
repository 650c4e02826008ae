"""Serving bench: open-loop load on the transform service -> BENCH_serve.json.

Two parts, both on an 8-virtual-device CPU mesh in a subprocess:

1. **Deterministic batching gate.**  The service's whole premise is that
   batched dispatch amortizes collectives: a (B, ...) stacked dispatch
   must compile to the SAME per-stage collective count as a single
   request, with bytes scaling exactly xB (collective amortization is
   structural, not a scheduling accident).  The gate compares post-SPMD
   HLO collective stats of the B=1 and B=4 executables for a c2c and a
   packed r2c plan and FAILS the bench (and CI) on any mismatch.

2. **Open-loop load sweep.**  Poisson arrivals at fixed offered QPS
   drive a mixed workload (c2c 32^3, r2c 32^3, filtered c2c 16^3)
   through ``TransformService``; requests are timed end to end (submit
   -> host result, including H2D/D2H).  Reported per point: p50/p99
   latency, achieved QPS, batch occupancy (real rows / padded rows).
   Plus the plan-cache hit rate split into the cold phase (first
   requests pay ``mode="wisdom"``->model planning) and the steady state.

Wall-clock numbers are recorded but non-gating: this container
schedules 8 device threads on ~2 cores (the PR 5 caveat), so absolute
latencies track host load, not the code.  The gate is part 1.

``run(smoke=True)`` is the CI path (fewer QPS points, shorter windows).

The service's metrics-registry snapshot (counters, padding waste, batch
size / latency / queue-wait histograms) is always recorded under
``metrics`` in ``BENCH_serve.json``.  ``run(trace=...)`` (CLI: ``--trace
out.json``) additionally enables the ``repro.obs`` tracer for the load
sweep — request-lifecycle spans (submit -> queue wait -> dispatch ->
h2d/compute/d2h) plus plan-cache events — records the per-category time
rollup under ``phase_rollup``, and saves the Chrome trace.
"""

from __future__ import annotations

import json
import os

from benchmarks.common import REPO, emit, run_subprocess_bench

BENCH_JSON = os.path.join(REPO, "BENCH_serve.json")

_BENCH_CODE = """
import json, os, tempfile, time
import numpy as np, jax, jax.numpy as jnp

from repro.core import Croft3D
from repro.launch import hlo_cost
from repro.launch.mesh import make_mesh
from repro.serve import PlanCache, TransformService

SMOKE = {smoke}
TRACE = {trace!r}
tracer = None
if TRACE:
    from repro import obs
    tracer = obs.enable()
mesh = make_mesh((2, 4), ("y", "z"))
wisdom = os.path.join(tempfile.mkdtemp(), "serve_wisdom.json")
report = {{"backend": jax.default_backend(),
           "mesh": dict(mesh.shape),
           "caveat": ("8 virtual devices on a ~2-core host: wall-clock "
                      "latency tracks host load; the deterministic gate "
                      "is the HLO collective-count comparison"),
           }}

# ---- part 1: deterministic collective-amortization gate -------------------
cache = PlanCache(mesh, wisdom_path=wisdom)
GATE_B = 4
gate = {{"batch": GATE_B, "cases": {{}}, "ok": True}}
from repro.core import Decomposition
gate_plans = [
    # the tuner-picked c2c plan the service itself would dispatch
    ("c2c", cache.get((32, 32, 32), np.complex64, "c2c").plan),
    # packed r2c forced explicitly: its batched path is the NATIVE
    # leading-batch pipeline (not vmap), the stronger claim to gate
    ("r2c", Croft3D((32, 32, 32), mesh,
                    Decomposition("pencil", ("y", "z")),
                    problem="r2c", strategy="packed")),
]
for problem, plan in gate_plans:
    single = hlo_cost.analyze(
        plan.lower_forward().compile().as_text()).collectives

    def batched_collectives(B):
        fn = plan._batched_fn("forward")
        spec = jax.ShapeDtypeStruct((B,) + plan.shape, plan.input_dtype,
                                    sharding=plan.batched_sharding("input"))
        return hlo_cost.analyze(fn.lower(spec).compile().as_text()
                                ).collectives

    case = {{"single": single}}
    for B in (1, GATE_B):
        got = batched_collectives(B)
        case[f"batched_b{{B}}"] = got
        counts_ok = (set(got) == set(single) and all(
            got[k]["count"] == single[k]["count"] for k in single))
        bytes_ok = all(got[k]["bytes"] == B * single[k]["bytes"]
                       for k in single)
        case[f"b{{B}}_count_equal"] = counts_ok
        case[f"b{{B}}_bytes_scale_exact"] = bytes_ok
        gate["ok"] = gate["ok"] and counts_ok and bytes_ok
    gate["cases"][f"{{problem}}/{{plan.strategy or 'c2c'}}"] = case
report["gate"] = gate

# ---- part 2: open-loop load sweep -----------------------------------------
rng = np.random.RandomState(0)
N_BIG, N_SMALL = 32, 16
fields = {{
    "c2c32": ((rng.randn(N_BIG, N_BIG, N_BIG)
               + 1j * rng.randn(N_BIG, N_BIG, N_BIG)).astype(np.complex64),
              dict(problem="c2c")),
    "r2c32": (rng.randn(N_BIG, N_BIG, N_BIG).astype(np.float32),
              dict(problem="r2c")),
    "filt16": ((rng.randn(N_SMALL, N_SMALL, N_SMALL)
                + 1j * rng.randn(N_SMALL, N_SMALL, N_SMALL)
                ).astype(np.complex64),
               dict(problem="filtered",
                    h=rng.randn(N_SMALL, N_SMALL, N_SMALL
                                ).astype(np.complex64))),
}}
MIX = ["c2c32", "c2c32", "c2c32", "r2c32", "r2c32", "filt16"]
QPS_POINTS = (20.0, 60.0) if SMOKE else (10.0, 30.0, 100.0)
DURATION = 2.0 if SMOKE else 5.0

svc = TransformService(mesh, max_batch=4, max_wait_ms=3.0, cache=cache)
svc.start()

# cold phase: first request per key pays wisdom/model planning + compile;
# also warms every (bucket-size) executable so the timed phase measures
# serving, not XLA compiles
cold_stats0 = dict(hits=cache.stats.hits, misses=cache.stats.misses)
for name, (x, kw) in fields.items():
    for wave in (1, 2, 4):
        futs = [svc.submit(x, **kw) for _ in range(wave)]
        for f in futs:
            r = f.result(timeout=300)
            assert r.ok, r.error
cold = {{"misses": cache.stats.misses - cold_stats0["misses"],
         "hits": cache.stats.hits - cold_stats0["hits"]}}

points = []
for qps in QPS_POINTS:
    arrivals = np.cumsum(rng.exponential(1.0 / qps,
                                         size=int(qps * DURATION)))
    pre = svc.stats()
    pre_cache = dict(cache.stats.as_dict())
    futs = []
    t0 = time.monotonic()
    for i, t_arr in enumerate(arrivals):
        delay = t0 + t_arr - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        x, kw = fields[MIX[i % len(MIX)]]
        futs.append(svc.submit(x, **kw))
    results = [f.result(timeout=300) for f in futs]
    t_total = time.monotonic() - t0
    assert all(r.ok for r in results)
    post = svc.stats()
    post_cache = dict(cache.stats.as_dict())
    lats = sorted(r.latency_s for r in results)
    d_real = post["real_rows"] - pre["real_rows"]
    d_batches = post["batches"] - pre["batches"]
    d_padded = post["padded_rows"] - pre["padded_rows"]
    points.append({{
        "offered_qps": qps,
        "achieved_qps": len(results) / t_total,
        "n_requests": len(results),
        "p50_ms": lats[len(lats) // 2] * 1e3,
        "p99_ms": lats[min(len(lats) - 1, int(0.99 * len(lats)))] * 1e3,
        "occupancy": d_real / d_padded if d_padded else None,
        "mean_batch": d_real / d_batches if d_batches else None,
        "steady_hit_rate": (
            (post_cache["hits"] - pre_cache["hits"])
            / max(1, (post_cache["hits"] - pre_cache["hits"]
                      + post_cache["misses"] - pre_cache["misses"]))),
    }})
report["load"] = {{"duration_s": DURATION, "mix": MIX, "points": points,
                  "cold_phase": cold}}
report["service_stats"] = svc.stats()
svc.stop()
report["plan_cache"] = cache.snapshot()
# per-phase breakdown: the registry snapshot is the always-on view
# (counters + batch/latency/queue-wait histograms with quantiles);
# plan-cache lifecycle counters live in the cache's own registry here
# because this bench builds the cache standalone
report["metrics"] = svc.registry.snapshot()
report["plan_cache_metrics"] = cache.registry.snapshot()
if tracer is not None:
    from repro.obs import report as obs_report
    report["phase_rollup"] = obs_report.category_rollup(tracer.events())
    tracer.save(TRACE)
    print("TRACE_WRITTEN " + TRACE)
print("SERVE_JSON " + json.dumps(report, default=float))
"""


def run(smoke: bool = False, trace: str | None = None) -> dict:
    out = run_subprocess_bench(
        _BENCH_CODE.format(smoke=repr(bool(smoke)), trace=trace),
        n_devices=8, timeout=1800)
    if trace and "TRACE_WRITTEN" not in out:
        raise RuntimeError("serve bench did not write the trace JSON")
    line = next(ln for ln in out.splitlines()
                if ln.startswith("SERVE_JSON "))
    report = json.loads(line[len("SERVE_JSON "):])

    for point in report["load"]["points"]:
        qps = point["offered_qps"]
        emit(f"serve/p50@q{qps:g}", point["p50_ms"] * 1e3, derived=False)
        emit(f"serve/p99@q{qps:g}", point["p99_ms"] * 1e3, derived=False)
    occ = [p["occupancy"] for p in report["load"]["points"]
           if p["occupancy"]]
    if occ:
        emit("serve/occupancy_max_pct", max(occ) * 100.0, derived=False)
    hit = report["plan_cache"]["stats"]["hit_rate"]
    emit("serve/plan_cache_hit_pct", hit * 100.0, derived=False)

    with open(BENCH_JSON, "w") as f:
        json.dump(report, f, indent=1, sort_keys=True)
    print(f"# wrote {BENCH_JSON}")

    gate = report["gate"]
    if not gate["ok"]:
        raise RuntimeError(
            "serve batching gate FAILED: batched dispatch does not "
            "compile to the single-request collective profile — "
            + json.dumps(gate["cases"]))
    print(f"# gate OK: batched B={gate['batch']} dispatch compiles to the "
          "same collective counts as one request (bytes scale exactly xB) "
          "for c2c and packed r2c")
    return report


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--trace", metavar="OUT.json", default=None,
                    help="enable the obs tracer for the load sweep and "
                         "save the Chrome trace here")
    args = ap.parse_args()
    run(smoke=args.smoke, trace=args.trace)
