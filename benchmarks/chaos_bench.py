"""Chaos bench: a seeded fault script against the serving SLO ->
BENCH_chaos.json.

One 8-virtual-device subprocess runs a deterministic fault script
through ``TransformService`` (``repro.resil.inject`` arms every fault at
an exact invocation index, so the prediction is computable before the
run) and the gate demands:

  * **zero hung futures** — every submitted future resolves;
  * **healthy availability 100%** — every request the script did NOT
    target succeeds, bitwise-equal to the direct plan call where a
    reference is computed;
  * **exact event accounting** — observed metrics counters equal the
    script's prediction exactly (one injected fault -> one retry /
    quarantine / shed / degradation event, never zero, never double);
  * **degradation parity** — after the scripted quarantine the degraded
    bucket's results equal the direct bottom-rung plan bit for bit.

The script (see ``_BENCH_CODE``):

  A. transient dispatch faults on the r2c bucket at invocations (0, 1)
     -> exactly 2 retries, then success;
  B. persistent dispatch faults on the primary c2c bucket with
     ``quarantine_after=2`` -> 2 failures, 1 quarantine, 1 degradation,
     then bitwise-parity service on the default rung;
  C. one NaN payload co-batched with two healthy requests -> 1 poisoned
     isolation, 2 individual re-dispatches, healthy results intact;
  D. a deadline storm (6 requests with ``deadline_s=0``) -> 6 typed
     deadline misses, nothing dispatched;
  E. bounded-queue shedding (``max_queue=4``, 4 HIGH + 3 LOW pending)
     -> exactly the 3 LOWs shed with typed queue-full results;
  F. one wisdom-store corruption + one crash-mid-write -> 1 quarantined
     ``.corrupt-1`` file, store stays loadable, stale temp cleaned.
"""

from __future__ import annotations

import json
import os

from benchmarks.common import REPO, emit, run_subprocess_bench

BENCH_JSON = os.path.join(REPO, "BENCH_chaos.json")

_BENCH_CODE = """
import json, os, tempfile, time
import numpy as np, jax

from repro.core import Croft3D
from repro.obs import metrics as metrics_lib
from repro.resil import (CrashMidWrite, FaultSpec, degrade, injection)
from repro.serve import (PRIORITY_HIGH, PRIORITY_LOW, PlanCache, ShedResult,
                         TransformService)
from repro.tuning import wisdom as wisdom_lib
from repro.tuning.candidates import default_candidate
from repro.launch.mesh import make_mesh

SMOKE = {smoke}
N = 16
AXES = {{"y": 2, "z": 4}}
mesh = make_mesh((2, 4), ("y", "z"))
rng = np.random.RandomState(0)
xc = (rng.randn(N, N, N) + 1j * rng.randn(N, N, N)).astype(np.complex64)
xr = rng.randn(N, N, N).astype(np.float32)

report = {{"backend": jax.default_backend(), "mesh": dict(mesh.shape),
           "scenarios": {{}}}}
futures = []       # (label, future) — the zero-hung-futures ledger
healthy = []       # (label, ok, bitwise_ok_or_None) — availability ledger
predicted = {{}}   # counter name -> exact predicted value

def resolve(label, fut, timeout=300):
    futures.append(label)
    return fut.result(timeout=timeout)   # a hang fails the bench here

# the primary c2c plan comes from seeded wisdom (measured, so it is born
# "warm" and never arms a background upgrade): the stock K=2 candidate,
# one rung above the ladder's K=1 bottom
wisdom = os.path.join(tempfile.mkdtemp(), "w.json")
cand = default_candidate((N, N, N), AXES)
key_c2c = wisdom_lib.wisdom_key((N, N, N), AXES, np.complex64,
                                jax.default_backend())
wisdom_lib.merge_entries(wisdom, {{key_c2c:
    wisdom_lib.WisdomEntry.from_candidate(cand, source="measure",
                                          measured_s=1e-3)}})

reg = metrics_lib.MetricsRegistry()
cache = PlanCache(mesh, wisdom_path=wisdom, quarantine_after=2,
                  registry=reg)
svc = TransformService(mesh, max_batch=4, max_wait_ms=150.0, cache=cache,
                       registry=reg, retry_backoff_s=0.0)
svc.start()

# pre-build the primary plan so its pipeline token is known to the fault
# script (the scripted error matches the PRIMARY token only — after the
# quarantine swaps the bottom rung in, the bucket token changes and the
# fault stops matching, exactly like a plan-specific crash would)
cp0 = cache.get((N, N, N), np.complex64, "c2c")
token_c2c = cp0.plan_token

script = [
    FaultSpec("serve.dispatch", times=(0, 1), kind="transient",
              match="|r2c"),          # A: r2c bucket, attempts 0 and 1
    FaultSpec("serve.dispatch", times=(0, 1), kind="error",
              match=token_c2c),       # B: primary c2c bucket, 2 dispatches
]

with injection(script) as fault_plan:
    # --- A: transient faults retry with backoff, then succeed ----------
    r = resolve("A:r2c", svc.submit(xr, problem="r2c"))
    plan_r = cache.get((N, N, N), np.complex64, "r2c").plan
    ref_r = np.asarray(plan_r.forward(jax.device_put(
        xr.astype(plan_r.input_dtype), plan_r.input_sharding)))
    healthy.append(("A:r2c", r.ok, bool(np.array_equal(r.value, ref_r))))
    report["scenarios"]["A_transient_retry"] = {{
        "ok": r.ok, "retries_predicted": 2}}
    predicted["serve_dispatch_retries"] = 2

    # --- B: persistent faults -> quarantine -> degradation -------------
    fails = [resolve(f"B:storm{{i}}", svc.submit(xc)) for i in range(2)]
    assert all(not r.ok for r in fails), [r.error for r in fails]
    predicted["plan_dispatch_failures"] = 2
    predicted["plan_quarantines"] = 1
    predicted["plan_degradations"] = 1

bottom = degrade.bottom_candidate((N, N, N), AXES)
fallback = Croft3D((N, N, N), mesh, bottom.decomp, bottom.opts)
ref_c = np.asarray(fallback.forward(
    jax.device_put(xc, fallback.input_sharding)))
cp1 = cache.get((N, N, N), np.complex64, "c2c")
degraded = [resolve(f"B:degraded{{i}}", svc.submit(xc)) for i in range(2)]
parity = [bool(np.array_equal(r.value, ref_c)) for r in degraded]
for i, r in enumerate(degraded):
    healthy.append((f"B:degraded{{i}}", r.ok, parity[i]))
report["scenarios"]["B_quarantine_degrade"] = {{
    "primary_token": token_c2c, "degraded_rung": cp1.rung,
    "quarantined": cp1.quarantined, "fallback_parity": parity}}
assert cp1.rung == "default" and cp1.quarantined, cp1.rung

# --- C: NaN payload isolation on the (degraded) c2c bucket -------------
bad = xc.copy(); bad[0, 0, 0] = np.nan
f_bad = svc.submit(bad)
f_mates = [svc.submit(xc) for _ in range(2)]
rb = resolve("C:poisoned", f_bad)
assert not rb.ok and "poisoned payload" in rb.error, rb.error
for i, f in enumerate(f_mates):
    r = resolve(f"C:mate{{i}}", f)
    healthy.append((f"C:mate{{i}}", r.ok,
                    bool(np.array_equal(r.value, ref_c))))
predicted["serve_poisoned_requests"] = 1
predicted["serve_poison_redispatches"] = 2
predicted["serve_nan_outputs"] = 0
predicted["serve_failures"] = 2 + 1   # B's storm + C's poisoned request
report["scenarios"]["C_nan_isolation"] = {{"poisoned": 1, "redispatch": 2}}

# --- D: deadline storm (never dispatches, always typed) ----------------
DEADLINE_STORM = 6
miss_reasons = []
for i in range(DEADLINE_STORM):
    r = resolve(f"D:storm{{i}}", svc.submit(xc, deadline_s=0.0))
    miss_reasons.append(isinstance(r, ShedResult)
                        and r.shed_reason == "deadline")
assert all(miss_reasons), miss_reasons
predicted["serve_deadline_misses"] = DEADLINE_STORM
report["scenarios"]["D_deadline_storm"] = {{"misses": DEADLINE_STORM}}

# --- E: bounded-queue shedding (own meshless service: the 60s wait
#        budget keeps everything pending, so counts are exact) ----------
svc2 = TransformService(max_batch=8, max_wait_ms=60000.0, max_queue=4)
svc2.start()
M = 8
x8 = (rng.randn(M, M, M) + 1j * rng.randn(M, M, M)).astype(np.complex64)
highs = [svc2.submit(x8, priority=PRIORITY_HIGH) for _ in range(4)]
lows = [svc2.submit(x8, priority=PRIORITY_LOW) for _ in range(3)]
shed_ok = [isinstance(resolve(f"E:low{{i}}", f), ShedResult)
           for i, f in enumerate(lows)]
svc2.stop()  # drain serves the HIGHs
for i, f in enumerate(highs):
    r = resolve(f"E:high{{i}}", f)
    healthy.append((f"E:high{{i}}", r.ok, None))
assert all(shed_ok), shed_ok
report["scenarios"]["E_queue_shed"] = {{"shed": 3, "served": 4}}

svc.stop()

# --- F: wisdom corruption + crash-mid-write ----------------------------
blob = json.load(open(wisdom))
blob["entries"][key_c2c]["model_s"] = 1e9   # tamper; checksum now stale
json.dump(blob, open(wisdom, "w"))
w = wisdom_lib.Wisdom.load(wisdom)
corrupt_moved = os.path.exists(wisdom + ".corrupt-1")
assert len(w) == 0 and corrupt_moved
crashed = False
try:
    with injection([FaultSpec("wisdom.write.crash", times=(0,),
                              kind="crash")]) as crash_plan:
        wisdom_lib.merge_entries(wisdom, {{key_c2c:
            wisdom_lib.WisdomEntry.from_candidate(cand, source="model",
                                                  model_s=1e-3)}})
except CrashMidWrite:
    crashed = True
tmp_left = os.path.exists(wisdom + ".tmp")
wisdom_lib.merge_entries(wisdom, {{key_c2c:
    wisdom_lib.WisdomEntry.from_candidate(cand, source="model",
                                          model_s=1e-3)}})
rebuilt = sorted(wisdom_lib.Wisdom.load(wisdom).entries) == [key_c2c]
tmp_cleaned = not os.path.exists(wisdom + ".tmp")
assert crashed and tmp_left and rebuilt and tmp_cleaned
predicted["wisdom_corrupt_files"] = 1      # global registry
report["scenarios"]["F_wisdom"] = {{
    "corrupt_moved": corrupt_moved, "crash_left_tmp": tmp_left,
    "rebuilt": rebuilt, "tmp_cleaned": tmp_cleaned}}

# --- gates -------------------------------------------------------------
snap = reg.snapshot()
snap2 = svc2.registry.snapshot()
gsnap = metrics_lib.get_registry().snapshot()
predicted["serve_shed_requests"] = 3       # svc2 registry

def observed(name):
    # total events across both services + the global registry (the two
    # service registries are disjoint; wisdom/fault counters are global)
    return int(sum(s[name]["value"] for s in (snap, snap2, gsnap)
                   if name in s))

counters = {{name: {{"predicted": want, "observed": observed(name)}}
            for name, want in predicted.items()}}
counts_exact = all(c["predicted"] == c["observed"]
                   for c in counters.values())

# injected-fault accounting: every scripted index fired exactly once
fired = fault_plan.fired_counts()
fault_exact = (fired == {{"serve.dispatch": 4}}
               and fault_plan.predicted_counts()
               == {{"serve.dispatch": 4}})

availability = (sum(1 for _l, ok, _p in healthy if ok)
                / max(1, len(healthy)))
parity_ok = all(p for _l, _ok, p in healthy if p is not None)

report["gate"] = {{
    "futures_resolved": len(futures), "hung_futures": 0,
    "healthy_total": len(healthy), "availability": availability,
    "bitwise_parity": parity_ok, "counters": counters,
    "counters_exact": counts_exact,
    "faults_fired": fired, "faults_exact": fault_exact,
    "ok": bool(counts_exact and fault_exact and parity_ok
               and availability == 1.0),
}}
print("CHAOS_JSON " + json.dumps(report, default=float))
"""


def run(smoke: bool = False) -> dict:
    out = run_subprocess_bench(_BENCH_CODE.format(smoke=repr(bool(smoke))),
                               n_devices=8, timeout=1800)
    line = next(ln for ln in out.splitlines()
                if ln.startswith("CHAOS_JSON "))
    report = json.loads(line[len("CHAOS_JSON "):])

    gate = report["gate"]
    emit("chaos/availability_pct", gate["availability"] * 100.0,
         derived=False)
    emit("chaos/hung_futures", float(gate["hung_futures"]), derived=False)
    emit("chaos/counters_exact", float(gate["counters_exact"]),
         derived=False)

    with open(BENCH_JSON, "w") as f:
        json.dump(report, f, indent=1, sort_keys=True)
    print(f"# wrote {BENCH_JSON}")

    if not gate["ok"]:
        raise RuntimeError(
            "chaos gate FAILED: injected faults did not map 1:1 to "
            "observed resilience events — " + json.dumps(gate))
    print(f"# gate OK: {gate['futures_resolved']} futures resolved, "
          f"availability {gate['availability']:.0%}, every scripted fault "
          "accounted for exactly (retries/quarantines/sheds/degradations)")
    return report


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args()
    run(smoke=args.smoke)
