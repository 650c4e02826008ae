"""Trace smoke: capture a CI trace and validate it -> TRACE_smoke.json.

The observability acceptance path (ISSUE 7): one subprocess on the
8-virtual-device mesh enables the ``repro.obs`` tracer, then

  * runs a **tuned 32^3 forward** through the per-stage attribution
    re-driver (``obs.instrument.trace_forward``),
  * traces the two acceptance plans — pencil **alltoall-K2** and
    **ring-K1** — so the report carries an overlap-efficiency number
    for both,
  * drives a **short serve run** (5 ragged requests through
    ``TransformService``, max_batch=4) so request-lifecycle and
    plan-cache spans land in the same trace,

and saves the Chrome-trace JSON.  The parent then validates the
artifact the way a trace consumer would:

  schema   every event has ``name``/``ph``/``ts``/``pid``/``tid``, ``ph``
           in {"X", "i"}, a known category, non-negative ``dur``;
  spans    the number of distinct per-stage spans per traced plan
           equals that plan's schedule stage count (printed by the
           subprocess from the real ``Schedule``);
  report   ``repro.obs.report`` renders it, and the attribution
           metadata holds an overlap-efficiency number for both
           acceptance plans;
  serve    the request-lifecycle span names all appear.

CI uploads ``TRACE_smoke.json`` next to the ``BENCH_*.json`` artifacts;
load it in chrome://tracing / Perfetto or feed it to
``python -m repro.obs.report``.
"""

from __future__ import annotations

import json
import os

from benchmarks.common import REPO, emit, run_subprocess_bench

TRACE_JSON = os.path.join(REPO, "TRACE_smoke.json")

_CODE = """
import os, tempfile, numpy as np, jax, jax.numpy as jnp
from repro import obs
from repro.core import Croft3D, Decomposition, FFTOptions
from repro.obs import instrument
from repro.serve import TransformService
from repro.tuning.measure import _random_input
from repro.launch.mesh import make_mesh

tracer = obs.enable()
mesh = make_mesh((2, 4), ("y", "z"))
N = 32

# -- tuned 32^3 forward + the two acceptance plans -------------------------
plans = [("tuned-32", Croft3D.tuned((N, N, N), mesh, mode="model"))]
for label, impl, k in (("alltoall-k2", "alltoall", 2), ("ring-k1", "ring", 1)):
    plans.append((label, Croft3D(
        (N, N, N), mesh, Decomposition("pencil", ("y", "z")),
        FFTOptions(overlap_k=k, transpose_impl=impl,
                   output_layout="spectral"))))
for label, plan in plans:
    x = _random_input((N, N, N), jnp.complex64, plan.input_sharding)
    y, summary = instrument.trace_forward(plan, x, tracer=tracer, iters=2,
                                          label=label)
    np.testing.assert_allclose(np.asarray(jax.device_get(y)),
                               np.asarray(jax.device_get(plan.forward(x))),
                               rtol=2e-4, atol=2e-4)
    print("STAGECOUNT,%s,%d" % (label, len(plan._forward_schedule().stages)))
    print("EFF,%s,%s" % (label, summary["overall"]["efficiency"]))

# -- short serve run: 5 ragged requests, request-lifecycle spans -----------
rng = np.random.RandomState(0)
x = (rng.randn(N, N, N) + 1j * rng.randn(N, N, N)).astype(np.complex64)
wisdom = os.path.join(tempfile.mkdtemp(), "w.json")
with TransformService(mesh, max_batch=4, max_wait_ms=2.0,
                      wisdom_path=wisdom) as svc:
    futs = [svc.submit(x) for _ in range(5)]
    for f in futs:
        r = f.result(timeout=300)
        assert r.ok, r.error

tracer.save({out!r})
print("TRACE_WRITTEN")
"""

_SERVE_SPANS = ("request:submit", "request:queue", "batch:dispatch",
                "batch:compute", "batch:d2h")


def _validate(doc: dict, expected_stages: dict) -> list:
    """Schema + span-count checks; returns a list of failure strings."""
    from repro.obs import CATEGORIES
    fails = []
    events = doc.get("traceEvents")
    if not isinstance(events, list) or not events:
        return ["traceEvents missing or empty"]
    for ev in events:
        if ev.get("ph") not in ("X", "i"):
            fails.append(f"bad ph in {ev}")
        elif not isinstance(ev.get("name"), str) or not ev["name"]:
            fails.append(f"bad name in {ev}")
        elif ev.get("cat") not in CATEGORIES:
            fails.append(f"unknown category {ev.get('cat')!r}")
        elif not isinstance(ev.get("ts"), (int, float)) or ev["ts"] < 0:
            fails.append(f"bad ts in {ev['name']}")
        elif "pid" not in ev or "tid" not in ev:
            fails.append(f"missing pid/tid in {ev['name']}")
        elif ev["ph"] == "X" and ev.get("dur", -1) < 0:
            fails.append(f"bad dur in {ev['name']}")
        if fails:
            break  # one schema failure is enough signal
    for label, n_stages in expected_stages.items():
        got = {ev["args"].get("stage") for ev in events
               if ev.get("ph") == "X"
               and ev.get("args", {}).get("part") == "stage"
               and ev.get("args", {}).get("plan") == label}
        if len(got) != n_stages:
            fails.append(f"{label}: {len(got)} stage spans, schedule has "
                         f"{n_stages} stages")
    names = {ev["name"] for ev in events}
    for need in _SERVE_SPANS:
        if need not in names:
            fails.append(f"serve lifecycle span {need!r} missing")
    plans = {s.get("plan"): s for s in
             (doc.get("metadata") or {}).get("attribution") or []}
    for label in ("alltoall-k2", "ring-k1"):
        overall = (plans.get(label) or {}).get("overall") or {}
        if not isinstance(overall.get("efficiency"), float):
            fails.append(f"{label}: no overlap-efficiency in attribution")
    return fails


def run(smoke: bool = False) -> None:
    del smoke  # one size: the capture is already the fast CI shape
    out = run_subprocess_bench(_CODE.format(out=TRACE_JSON), n_devices=8,
                               timeout=1800)
    if "TRACE_WRITTEN" not in out:
        raise RuntimeError("trace smoke did not write the trace JSON")
    expected = {}
    for line in out.splitlines():
        if line.startswith("STAGECOUNT,"):
            _, label, n = line.split(",")
            expected[label] = int(n)
        elif line.startswith("EFF,"):
            _, label, eff = line.split(",")
            emit(f"trace/{label}/overlap-eff-pct", 100.0 * float(eff), True)

    with open(TRACE_JSON) as f:
        doc = json.load(f)
    fails = _validate(doc, expected)
    if fails:
        raise RuntimeError("trace validation FAILED: " + "; ".join(fails))

    # the report must render the artifact end to end (the acceptance CLI)
    from repro.obs import report as obs_report
    if obs_report.main([TRACE_JSON]) != 0:
        raise RuntimeError("repro.obs.report failed on the captured trace")
    emit("trace/n_events", len(doc["traceEvents"]), True)
    print(f"# wrote {TRACE_JSON} ({len(doc['traceEvents'])} events, "
          f"{len(expected)} plans attributed)")


if __name__ == "__main__":
    print("name,us_per_call,derived")
    run()
