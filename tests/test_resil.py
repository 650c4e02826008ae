"""repro.resil: seeded fault injection, degradation ladders, hardening.

Single-device tests drive the whole request-lifecycle surface (sheds,
deadlines, retries, NaN isolation, preemption, upgrade rollback, wisdom
integrity) on meshless plans; the distributed story — HLO byte-identity
with an armed injector, executor-output poisoning, quarantine -> ladder
degradation with bitwise fallback parity — runs once in an 8-virtual-
device subprocess, and a seeded chaos script over every fault kind runs
in one more.
"""

import json
import os
import signal
import threading
import time

import numpy as np
import pytest

from repro.core import Croft3D
from repro.resil import (CrashMidWrite, FaultPlan, FaultSpec, InjectedFault,
                         TransientFault, degrade, inject, injection,
                         seeded_times)
from repro.resil.fault import PreemptionHandler, StragglerMonitor
from repro.serve import (PRIORITY_HIGH, PRIORITY_LOW, PlanCache, ShedResult,
                         TransformService)
from repro.tuning import wisdom as wisdom_lib
from repro.tuning.candidates import default_candidate
from conftest import multidevice_report, run_multidevice

N = 8


def _cplx(rng, n=N):
    return (rng.randn(n, n, n) + 1j * rng.randn(n, n, n)).astype(np.complex64)


def _entry(measured=None):
    cand = default_candidate((8, 8, 8), {"y": 2, "z": 2})
    return wisdom_lib.WisdomEntry.from_candidate(
        cand, source="measure" if measured else "model",
        model_s=1e-3, measured_s=measured)


# --- fault plan mechanics ---------------------------------------------------

def test_fault_plan_times_and_match_are_exact():
    plan = FaultPlan([FaultSpec("serve.dispatch", times=(1,),
                                kind="transient"),
                      FaultSpec("plan.build", match="abc")])
    assert plan.check("serve.dispatch", "k") is None      # idx 0: scripted off
    spec, idx = plan.check("serve.dispatch", "k")         # idx 1: fires
    assert idx == 1 and spec.kind == "transient"
    assert plan.check("serve.dispatch", "k") is None      # idx 2: off again
    # match filters BEFORE the index counts: non-matching keys are
    # invisible to the spec's invocation stream
    assert plan.check("plan.build", "xyz") is None
    _spec, idx = plan.check("plan.build", "zzabczz")
    assert idx == 0
    assert plan.fired_counts() == {"serve.dispatch": 1, "plan.build": 1}
    # explicit times predict exactly; times=None predicts None (unknown)
    assert plan.predicted_counts() == {"serve.dispatch": 1,
                                       "plan.build": None}
    # un-scripted sites return None without bookkeeping
    assert plan.check("wisdom.write.crash", "p") is None


def test_fault_spec_validation_and_kinds():
    with pytest.raises(ValueError, match="kind"):
        FaultSpec("plan.build", kind="explode")
    with injection([FaultSpec("tune.measure", times=(0,))]) as plan:
        with pytest.raises(InjectedFault) as ei:
            inject.fire("tune.measure", "lbl")
        assert ei.value.site == "tune.measure" and ei.value.index == 0
        inject.fire("tune.measure", "lbl")  # idx 1: no-op
        assert plan.fired_counts() == {"tune.measure": 1}
    assert inject.get_plan() is None  # injection() always disarms
    with injection([FaultSpec("serve.dispatch", kind="transient"),
                    FaultSpec("wisdom.write.crash", kind="crash")]):
        with pytest.raises(TransientFault):
            inject.fire("serve.dispatch", "b")
        with pytest.raises(CrashMidWrite):
            inject.fire("wisdom.write.crash", "p")
    # disarmed: fire/corrupt are no-ops
    inject.fire("serve.dispatch", "b")
    assert inject.corrupt("exec.output", "s") is False


def test_seeded_times_deterministic():
    a = seeded_times(7, "serve.dispatch", 10, 3)
    assert a == seeded_times(7, "serve.dispatch", 10, 3)
    assert a != seeded_times(8, "serve.dispatch", 10, 3)
    assert a != seeded_times(7, "plan.build", 10, 3)
    assert len(a) == 3 and list(a) == sorted(set(a))
    assert all(0 <= t < 10 for t in a)


# --- degradation ladder (unit) ----------------------------------------------

def test_degrade_ladder_walks_to_default():
    axis_sizes = {"y": 2, "z": 2}
    cand = default_candidate((8, 8, 8), axis_sizes)
    bottom = degrade.bottom_candidate((8, 8, 8), axis_sizes)
    assert bottom.opts.overlap_k == 1
    assert bottom.opts.transpose_impl == "alltoall"
    # stock candidate (K=2) sits one rung above the bottom
    step = degrade.next_rung(cand, (8, 8, 8), axis_sizes)
    assert step is not None and step[0] == "default"
    assert step[1].plan_key == bottom.plan_key
    # the bottom itself has nowhere to go
    assert degrade.next_rung(bottom, (8, 8, 8), axis_sizes) is None
    # packed r2c degrades to embed before the default rung
    r2c = default_candidate((8, 8, 8), axis_sizes, problem="r2c")
    if getattr(r2c, "strategy", None) == "packed":
        rung, emb = degrade.next_rung(r2c, (8, 8, 8), axis_sizes)
        assert rung == "embed" and emb.strategy == "embed"
    rb = degrade.bottom_candidate((8, 8, 8), axis_sizes, problem="r2c")
    assert rb.strategy == "embed"


def test_degrade_meshless_plan_has_no_ladder():
    assert degrade.ladder(Croft3D((N, N, N))) == []


# --- plan-cache resilience (single device) ----------------------------------

def test_plan_build_fault_falls_back_and_serves(rng):
    cache = PlanCache()
    with injection([FaultSpec("plan.build", times=(0,))]):
        cp = cache.get((N, N, N))
    assert cp.rung == "default"
    snap = cache.registry.snapshot()
    assert snap["plan_build_failures"]["value"] == 1
    assert snap["plan_build_fallbacks"]["value"] == 1
    x = _cplx(rng)
    assert np.array_equal(np.asarray(cp.plan.forward(x)),
                          np.asarray(Croft3D((N, N, N)).forward(x)))
    # a fresh key after the scripted window builds primary again
    cp2 = cache.get((N, N, 2 * N))
    assert cp2.rung == "primary"


def test_quarantine_exhausted_resets_failure_counter():
    """A meshless plan has no ladder: quarantine bottoms out, counts one
    exhaustion event, and resets the burst counter (bounded events)."""
    cache = PlanCache(quarantine_after=3)
    cp = cache.get((N, N, N))
    for _ in range(3):
        cache.report_dispatch_failure(cp.key)
    snap = cache.registry.snapshot()
    assert snap["plan_dispatch_failures"]["value"] == 3
    assert snap["plan_quarantines"]["value"] == 1
    assert snap["plan_degrade_exhausted"]["value"] == 1
    assert cache._plans[cp.key].failures == 0
    assert cache._plans[cp.key].plan is cp.plan  # still serving


def test_upgrade_failure_rolls_back_and_caps_retries(rng):
    """Satellite 1: a failing background upgrade must roll the entry back
    to its servable cold state, count serve_upgrade_failures, and stop
    re-arming after upgrade_max_retries."""
    cache = PlanCache(measure_after=1, upgrade_async=False,
                      upgrade_max_retries=2)
    cp = cache.get((N, N, N))
    cp.state = "cold"           # meshless plans are born warm; force the
    cache.mesh = object()       # upgrade path (injection raises before
    #                             anything touches the fake mesh)
    with injection([FaultSpec("plan.upgrade")]) as plan:
        for _ in range(5):
            cache._maybe_upgrade(cache._plans[cp.key])
        assert plan.fired_counts() == {"plan.upgrade": 2}  # capped
    cur = cache._plans[cp.key]
    assert cur.upgrade_failures == 2 and not cur.upgrading
    assert cur.state == "cold"
    snap = cache.registry.snapshot()
    assert snap["serve_upgrade_failures"]["value"] == 2
    assert snap["plan_cache_upgrade_starts"]["value"] == 2
    x = _cplx(rng)  # the rolled-back entry still serves
    assert np.array_equal(np.asarray(cur.plan.forward(x)),
                          np.asarray(Croft3D((N, N, N)).forward(x)))


def test_wait_idle_reports_timeout_and_prunes():
    """Satellite 2: wait_idle says whether threads actually joined."""
    cache = PlanCache()
    assert cache.wait_idle(timeout=0.1) is True  # nothing outstanding
    t = threading.Thread(target=lambda: time.sleep(0.5), daemon=True)
    cache._upgrade_threads.append(t)
    t.start()
    assert cache.wait_idle(timeout=0.05) is False
    assert cache.alive_upgrades() == 1
    assert cache.wait_idle(timeout=10.0) is True
    assert cache.alive_upgrades() == 0
    assert cache._upgrade_threads == []


# --- service request lifecycle (single device) ------------------------------

def test_transient_dispatch_fault_retries_and_succeeds(rng):
    with injection([FaultSpec("serve.dispatch", times=(0,),
                              kind="transient")]):
        with TransformService(max_batch=4, retry_backoff_s=0.0) as svc:
            x = _cplx(rng)
            got = svc.transform(x)
            assert np.array_equal(got,
                                  np.asarray(Croft3D((N, N, N)).forward(x)))
            snap = svc.registry.snapshot()
            assert snap["serve_dispatch_retries"]["value"] == 1
            assert snap["serve_failures"]["value"] == 0


def test_transient_fault_exhausts_retries_then_fails(rng):
    with injection([FaultSpec("serve.dispatch", kind="transient")]):
        with TransformService(max_batch=4, dispatch_retries=1,
                              retry_backoff_s=0.0) as svc:
            r = svc.submit(_cplx(rng)).result(timeout=60)
            assert not r.ok and "TransientFault" in r.error
            snap = svc.registry.snapshot()
            assert snap["serve_dispatch_retries"]["value"] == 1
            # the exhausted failure counts toward quarantine
            assert snap["plan_dispatch_failures"]["value"] == 1


def test_deadline_miss_resolves_typed_and_batchmates_survive(rng):
    with TransformService(max_batch=4, max_wait_ms=20.0) as svc:
        f_dead = svc.submit(_cplx(rng), deadline_s=0.0)
        f_live = svc.submit(_cplx(rng))
        rd = f_dead.result(timeout=60)
        assert isinstance(rd, ShedResult) and rd.shed_reason == "deadline"
        assert not rd.ok and "deadline" in rd.error
        assert f_live.result(timeout=60).ok
        assert svc.registry.snapshot()["serve_deadline_misses"]["value"] == 1


def test_bounded_queue_sheds_lowest_priority_first(rng):
    """max_queue=4 with 4 HIGH + 3 LOW pending: exactly the 3 LOWs shed
    with a typed queue-full ShedResult; the HIGHs all serve on drain.
    max_wait is huge so nothing dispatches until stop() — counts exact."""
    with TransformService(max_batch=8, max_wait_ms=60000.0,
                          max_queue=4) as svc:
        highs = [svc.submit(_cplx(rng), priority=PRIORITY_HIGH)
                 for _ in range(4)]
        lows = [svc.submit(_cplx(rng), priority=PRIORITY_LOW)
                for _ in range(3)]
        shed = [f.result(timeout=60) for f in lows]  # resolve pre-stop:
        #                                              a shed never hangs
        assert all(isinstance(r, ShedResult)
                   and r.shed_reason == "queue-full" for r in shed)
        assert svc.registry.snapshot()["serve_shed_requests"]["value"] == 3
    assert all(f.result(timeout=60).ok for f in highs)


def test_nan_payload_isolated_healthy_batchmates_redispatch(rng):
    """One NaN payload co-batched with two healthy requests: the poisoned
    request fails typed, both batch-mates re-dispatch individually and
    come back bitwise-equal to the direct transform."""
    xs = [_cplx(rng) for _ in range(2)]
    bad = _cplx(rng)
    bad[0, 0, 0] = np.nan
    ref = Croft3D((N, N, N))
    with TransformService(max_batch=4, max_wait_ms=200.0) as svc:
        fb = svc.submit(bad)
        fh = [svc.submit(x) for x in xs]
        rb = fb.result(timeout=120)
        assert not rb.ok and "poisoned payload" in rb.error
        for x, f in zip(xs, fh):
            r = f.result(timeout=120)
            assert r.ok, r.error
            assert np.array_equal(r.value, np.asarray(ref.forward(x)))
        snap = svc.registry.snapshot()
        assert snap["serve_poisoned_requests"]["value"] == 1
        assert snap["serve_poison_redispatches"]["value"] == 2


def test_straggler_monitor_flags_outlier():
    mon = StragglerMonitor(z_threshold=3.0, warmup_steps=2)
    flagged = []
    mon.on_straggler = flagged.append
    for i in range(10):
        mon.start_step()
        mon._t0 -= 0.1  # simulate 100ms step
        mon.end_step(i)
    mon.start_step()
    mon._t0 -= 3.0      # 3s straggler
    st = mon.end_step(99)
    assert st.is_straggler and flagged and flagged[0].step == 99


def test_preemption_handler_flag():
    h = PreemptionHandler()
    assert not h.preemption_requested
    h._handle(15, None)
    assert h.preemption_requested


def test_preemption_drains_and_refuses_new_work(rng):
    """Satellite 3: SIGTERM flips the PreemptionHandler flag; the worker
    serves everything pending, stops cleanly, and submit() refuses."""
    old = signal.getsignal(signal.SIGTERM)
    try:
        svc = TransformService(max_batch=8, max_wait_ms=60000.0,
                               preemption=PreemptionHandler())
        svc.start()
        futs = [svc.submit(_cplx(rng)) for _ in range(3)]
        signal.raise_signal(signal.SIGTERM)
        results = [f.result(timeout=120) for f in futs]
        assert all(r.ok for r in results), [r.error for r in results]
        t0 = time.monotonic()
        while svc._worker.is_alive() and time.monotonic() - t0 < 30:
            time.sleep(0.01)
        assert not svc._worker.is_alive(), "worker did not stop after drain"
        with pytest.raises(RuntimeError, match="not started"):
            svc.submit(_cplx(rng))
        assert svc.registry.snapshot()[
            "serve_preemption_drains"]["value"] == 1
        svc.stop()  # idempotent after the drain
    finally:
        signal.signal(signal.SIGTERM, old)


# --- wisdom integrity -------------------------------------------------------

def test_wisdom_checksum_corruption_quarantines_file(tmp_path):
    path = str(tmp_path / "w.json")
    wisdom_lib.merge_entries(path, {"ka": _entry()})
    blob = json.load(open(path))
    assert blob["checksum"] == wisdom_lib._entries_checksum(blob["entries"])
    blob["entries"]["ka"]["model_s"] = 99.0  # tamper, keep stale checksum
    json.dump(blob, open(path, "w"))
    assert len(wisdom_lib.Wisdom.load(path)) == 0
    assert os.path.exists(path + ".corrupt-1") and not os.path.exists(path)
    with open(path, "w") as f:
        f.write("{ not json")  # parse failure quarantines too
    assert len(wisdom_lib.Wisdom.load(path)) == 0
    assert os.path.exists(path + ".corrupt-2")


def test_wisdom_legacy_and_newer_version_files(tmp_path):
    path = str(tmp_path / "w.json")
    wisdom_lib.merge_entries(path, {"kb": _entry()})
    blob = json.load(open(path))
    del blob["checksum"]  # pre-checksum file: nothing to verify
    json.dump(blob, open(path, "w"))
    assert sorted(wisdom_lib.Wisdom.load(path).entries) == ["kb"]
    # a newer-version file is valid-but-unknown: empty, NOT quarantined
    json.dump({"version": 99, "entries": {}}, open(path, "w"))
    assert len(wisdom_lib.Wisdom.load(path)) == 0
    assert os.path.exists(path)
    assert not any(p.name.endswith(".corrupt-1")
                   for p in tmp_path.iterdir())


def test_wisdom_crash_mid_write_leaves_store_loadable(tmp_path):
    """Satellite 4: a writer killed between temp-write and atomic rename
    leaves the old store intact plus a stale .tmp; the next locked merge
    cleans the temp and lands both entries."""
    path = str(tmp_path / "w.json")
    wisdom_lib.merge_entries(path, {"k1": _entry()})
    with injection([FaultSpec("wisdom.write.crash", times=(0,),
                              kind="crash")]):
        with pytest.raises(CrashMidWrite):
            wisdom_lib.merge_entries(path, {"k2": _entry(measured=1e-3)})
    assert os.path.exists(path + ".tmp")  # the interrupted write
    assert sorted(wisdom_lib.Wisdom.load(path).entries) == ["k1"]
    wisdom_lib.merge_entries(path, {"k2": _entry(measured=1e-3)})
    assert not os.path.exists(path + ".tmp")
    assert sorted(wisdom_lib.Wisdom.load(path).entries) == ["k1", "k2"]
    assert not os.path.exists(path + ".lock")


# --- distributed: HLO pin, executor poisoning, ladder parity ----------------

_MULTIDEVICE_CODE = """
import dataclasses, os, tempfile
import numpy as np, jax
from repro.core import Croft3D, Decomposition, FFTOptions
from repro.obs.metrics import MetricsRegistry
from repro.resil import FaultSpec, degrade, injection
from repro.serve import PlanCache, TransformService
from repro.tuning import wisdom as wisdom_lib
from repro.tuning.candidates import default_candidate
from repro.launch.mesh import make_mesh

mesh = make_mesh((2, 4), ("y", "z"))
N = 16
dec = Decomposition("pencil", ("y", "z"))

# HLO pin: an armed-but-unmatched injector contributes zero ops — a plan
# compiled under it is byte-identical to one compiled with no injector,
# up to the source lines in the location table that compiled HLO text
# opens with (FileNames ... StackFrames, before the first computation):
# the two plans are built on different lines of this script
def hlo_split(plan):
    lines = plan.lower_forward().compile().as_text().splitlines()
    end = next(i for i, l in enumerate(lines)
               if l.startswith(("%", "ENTRY")))
    start = lines.index("FileNames") if "FileNames" in lines[:end] else end
    return lines[:start] + lines[end:], lines[start:end]

pa = Croft3D((N, N, N), mesh, dec, FFTOptions(overlap_k=2))
ops_off, locs_off = hlo_split(pa)
with injection([FaultSpec("exec.output", match="no-such-schedule")]):
    pb = Croft3D((N, N, N), mesh, dec, FFTOptions(overlap_k=2))
    ops_on, locs_on = hlo_split(pb)
assert ops_on == ops_off, "armed injector changed compiled HLO"
assert len(locs_on) == len(locs_off), "location tables differ in size"
assert all("file_name_id=" in a for a, b in zip(locs_on, locs_off)
           if a != b), "location tables differ outside source lines"

# executor-output poisoning: finite input -> NaN output is treated as a
# poisoned plan; at quarantine_after=1 the entry degrades to the bottom
# rung, whose results must equal the direct fallback plan bit for bit
wisdom = os.path.join(tempfile.mkdtemp(), "w.json")
cand = default_candidate((N, N, N), {"y": 2, "z": 2})
key = wisdom_lib.wisdom_key((N, N, N), {"y": 2, "z": 2}, np.complex64,
                            jax.default_backend())
wisdom_lib.merge_entries(wisdom, {key: wisdom_lib.WisdomEntry.from_candidate(
    cand, source="measure", measured_s=1e-3)})

reg = MetricsRegistry()
cache = PlanCache(mesh, wisdom_path=wisdom, quarantine_after=1,
                  registry=reg)
svc = TransformService(mesh, max_batch=4, max_wait_ms=20.0, cache=cache,
                       registry=reg)
rng = np.random.RandomState(0)
x = (rng.randn(N, N, N) + 1j * rng.randn(N, N, N)).astype(np.complex64)
with svc:
    with injection([FaultSpec("exec.output", kind="nan")]):
        r = svc.submit(x).result(timeout=400)
    assert not r.ok and "non-finite output" in r.error, r.error
    snap = svc.registry.snapshot()
    assert snap["serve_nan_outputs"]["value"] == 1
    assert snap["plan_quarantines"]["value"] == 1
    assert snap["plan_degradations"]["value"] == 1
    cp = cache._plans[cache.key_for((N, N, N), np.complex64, "c2c")]
    assert cp.rung == "default" and cp.quarantined
    r2 = svc.submit(x).result(timeout=400)
    assert r2.ok, r2.error
    bottom = degrade.bottom_candidate((N, N, N), {"y": 2, "z": 2})
    direct = Croft3D((N, N, N), mesh, bottom.decomp, bottom.opts)
    ref = np.asarray(direct.forward(
        jax.device_put(x, direct.input_sharding)))
    assert np.array_equal(r2.value, ref), "degraded bucket != fallback plan"
print("RESIL_MULTIDEVICE_OK")
"""


def test_resil_multidevice_poison_quarantine_parity():
    out = run_multidevice(_MULTIDEVICE_CODE, n_devices=8, timeout=480)
    assert "RESIL_MULTIDEVICE_OK" in out


# --- the seeded chaos script ------------------------------------------------
#
# One deterministic fault script through a TransformService on the 2x4
# mesh.  Every fault is armed at an exact invocation index, so the
# resilience events it must cause are known before the run:
#   A. transient dispatch faults on the r2c bucket at attempts 0, 1
#      -> 2 retries, then success;
#   B. persistent dispatch faults on the primary c2c bucket with
#      quarantine_after=2 -> 2 failures, 1 quarantine, 1 degradation,
#      then bitwise fallback parity;
#   C. one NaN payload co-batched with two healthy requests -> 1 poisoned
#      request, 2 individual re-dispatches;
#   D. a deadline storm (6 requests, deadline_s=0) -> 6 typed misses;
#   E. bounded-queue shedding (max_queue=4, 4 HIGH + 3 LOW pending)
#      -> exactly the 3 LOWs shed;
#   F. one wisdom-store corruption + one crash mid-write -> 1 quarantined
#      file, the store stays loadable.

_CHAOS_CODE = """
import concurrent.futures, json, os, tempfile
import numpy as np, jax

from repro.core import Croft3D
from repro.obs import metrics as metrics_lib
from repro.resil import CrashMidWrite, FaultSpec, degrade, injection
from repro.serve import (PRIORITY_HIGH, PRIORITY_LOW, PlanCache, ShedResult,
                         TransformService)
from repro.tuning import wisdom as wisdom_lib
from repro.tuning.candidates import default_candidate
from repro.launch.mesh import make_mesh

N = 16
AXES = {"y": 2, "z": 4}
mesh = make_mesh((2, 4), ("y", "z"))
rng = np.random.RandomState(0)
xc = (rng.randn(N, N, N) + 1j * rng.randn(N, N, N)).astype(np.complex64)
xr = rng.randn(N, N, N).astype(np.float32)

resolved, hung = [], []
healthy = []     # (label, ok, bitwise parity or None)
predicted = {}   # counter name -> exact predicted value

def resolve(label, fut):
    try:
        r = fut.result(timeout=120)
    except concurrent.futures.TimeoutError:
        hung.append(label)
        return None
    resolved.append(label)
    return r

def served(label, r, ref=None):
    ok = r is not None and r.ok
    healthy.append((label, ok, None if ref is None
                    else ok and bool(np.array_equal(r.value, ref))))

# the primary c2c plan comes from measured wisdom, so it is born warm
# and never arms a background upgrade: the stock K=2 candidate, one rung
# above the ladder's K=1 bottom
wisdom = os.path.join(tempfile.mkdtemp(), "w.json")
cand = default_candidate((N, N, N), AXES)
key_c2c = wisdom_lib.wisdom_key((N, N, N), AXES, np.complex64,
                                jax.default_backend())
wisdom_lib.merge_entries(wisdom, {key_c2c: wisdom_lib.WisdomEntry.from_candidate(
    cand, source="measure", measured_s=1e-3)})

reg = metrics_lib.MetricsRegistry()
cache = PlanCache(mesh, wisdom_path=wisdom, quarantine_after=2, registry=reg)
svc = TransformService(mesh, max_batch=4, max_wait_ms=150.0, cache=cache,
                       registry=reg, retry_backoff_s=0.0)
svc.start()
# the scripted error matches the primary plan's token only: once the
# quarantine swaps the bottom rung in, the fault stops matching
token_c2c = cache.get((N, N, N), np.complex64, "c2c").plan_token
script = [
    FaultSpec("serve.dispatch", times=(0, 1), kind="transient", match="|r2c"),
    FaultSpec("serve.dispatch", times=(0, 1), kind="error", match=token_c2c),
]
with injection(script) as fault_plan:
    r = resolve("A:r2c", svc.submit(xr, problem="r2c"))           # A
    plan_r = cache.get((N, N, N), np.complex64, "r2c").plan
    served("A:r2c", r, np.asarray(plan_r.forward(jax.device_put(
        xr.astype(plan_r.input_dtype), plan_r.input_sharding))))
    predicted["serve_dispatch_retries"] = 2
    storm = [resolve(f"B:storm{i}", svc.submit(xc)) for i in range(2)]  # B
    predicted["plan_dispatch_failures"] = 2
    predicted["plan_quarantines"] = 1
    predicted["plan_degradations"] = 1

bottom = degrade.bottom_candidate((N, N, N), AXES)
fallback = Croft3D((N, N, N), mesh, bottom.decomp, bottom.opts)
ref_c = np.asarray(fallback.forward(jax.device_put(xc, fallback.input_sharding)))
cp = cache.get((N, N, N), np.complex64, "c2c")
for i in range(2):
    served(f"B:degraded{i}", resolve(f"B:degraded{i}", svc.submit(xc)), ref_c)

bad = xc.copy()                                                       # C
bad[0, 0, 0] = np.nan
f_bad = svc.submit(bad)
f_mates = [svc.submit(xc) for _ in range(2)]
r_bad = resolve("C:poisoned", f_bad)
for i, f in enumerate(f_mates):
    served(f"C:mate{i}", resolve(f"C:mate{i}", f), ref_c)
predicted["serve_poisoned_requests"] = 1
predicted["serve_poison_redispatches"] = 2
predicted["serve_nan_outputs"] = 0
predicted["serve_failures"] = 2 + 1   # B's storm + C's poisoned request

misses = [resolve(f"D:storm{i}", svc.submit(xc, deadline_s=0.0))      # D
          for i in range(6)]
predicted["serve_deadline_misses"] = 6

# E: a meshless service whose 60 s wait budget keeps everything pending
svc2 = TransformService(max_batch=8, max_wait_ms=60000.0, max_queue=4)
svc2.start()
x8 = (rng.randn(8, 8, 8) + 1j * rng.randn(8, 8, 8)).astype(np.complex64)
highs = [svc2.submit(x8, priority=PRIORITY_HIGH) for _ in range(4)]
lows = [resolve(f"E:low{i}", svc2.submit(x8, priority=PRIORITY_LOW))
        for i in range(3)]
svc2.stop()  # the drain serves the HIGHs
for i, f in enumerate(highs):
    served(f"E:high{i}", resolve(f"E:high{i}", f))
predicted["serve_shed_requests"] = 3
svc.stop()

blob = json.load(open(wisdom))                                        # F
blob["entries"][key_c2c]["model_s"] = 1e9   # tamper: checksum now stale
json.dump(blob, open(wisdom, "w"))
loaded_after_tamper = len(wisdom_lib.Wisdom.load(wisdom))
entry = wisdom_lib.WisdomEntry.from_candidate(cand, source="model",
                                              model_s=1e-3)
crashed = False
try:
    with injection([FaultSpec("wisdom.write.crash", times=(0,),
                              kind="crash")]):
        wisdom_lib.merge_entries(wisdom, {key_c2c: entry})
except CrashMidWrite:
    crashed = True
tmp_left = os.path.exists(wisdom + ".tmp")
wisdom_lib.merge_entries(wisdom, {key_c2c: entry})
predicted["wisdom_corrupt_files"] = 1

snaps = (reg.snapshot(), svc2.registry.snapshot(),
         metrics_lib.get_registry().snapshot())
observed = {name: int(sum(s[name]["value"] for s in snaps if name in s))
            for name in predicted}
print("REPORT " + json.dumps({
    "resolved": len(resolved), "hung": hung,
    "healthy": healthy, "predicted": predicted, "observed": observed,
    "fired": fault_plan.fired_counts(),
    "predicted_fired": fault_plan.predicted_counts(),
    "storm_failed": [r is not None and not r.ok for r in storm],
    "degraded": [cp.rung, cp.quarantined],
    "poisoned": r_bad is not None and not r_bad.ok
                and "poisoned payload" in r_bad.error,
    "deadline_misses": [isinstance(r, ShedResult)
                        and r.shed_reason == "deadline" for r in misses],
    "lows_shed": [isinstance(r, ShedResult) for r in lows],
    "wisdom": {"tamper_quarantined": loaded_after_tamper == 0
                   and os.path.exists(wisdom + ".corrupt-1"),
               "crash_left_tmp": crashed and tmp_left,
               "rebuilt": sorted(wisdom_lib.Wisdom.load(wisdom).entries)
                   == [key_c2c],
               "tmp_cleaned": not os.path.exists(wisdom + ".tmp")},
}))
"""


@pytest.fixture(scope="module")
def chaos():
    return multidevice_report(_CHAOS_CODE)


def test_chaos_script_counters_match_prediction(chaos):
    """Every injected fault maps to exactly one retry / quarantine /
    shed / degradation event: never zero, never two."""
    assert chaos["observed"] == chaos["predicted"]
    assert chaos["storm_failed"] == [True, True]
    assert chaos["degraded"] == ["default", True]
    assert chaos["poisoned"]
    assert chaos["deadline_misses"] == [True] * 6
    assert chaos["lows_shed"] == [True] * 3
    assert all(chaos["wisdom"].values()), chaos["wisdom"]


def test_chaos_script_faults_fired_as_predicted(chaos):
    assert chaos["fired"] == chaos["predicted_fired"] == {"serve.dispatch": 4}


def test_chaos_script_healthy_requests_available_and_bitwise(chaos):
    """Every request the script did not target succeeds, bitwise equal
    to the direct plan call (the degraded bucket to the bottom rung)."""
    healthy = chaos["healthy"]
    assert len(healthy) == 1 + 2 + 2 + 4
    assert all(ok for _, ok, _ in healthy), healthy
    assert all(p for _, _, p in healthy if p is not None), healthy


def test_chaos_script_leaves_no_hung_future(chaos):
    assert chaos["hung"] == []
    assert chaos["resolved"] == 1 + 2 + 2 + 1 + 2 + 6 + 3 + 4
