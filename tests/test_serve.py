"""repro.serve: batching policy, plan cache, service correctness.

Single-device tests run the real service (meshless plans compile in
milliseconds at 8^3/16^3); the distributed path — batched dispatch on a
2x4 pencil mesh with cold->warm measurement upgrades and LRU eviction —
runs once in an 8-virtual-device subprocess.
"""

import json
import os
import threading
import time

import numpy as np
import pytest

from repro.core import Croft3D
from repro.serve import (Batcher, PlanCache, TransformRequest,
                         TransformService, bucket_key, padded_size,
                         stack_and_pad)
from repro.tuning import wisdom as wisdom_lib
from conftest import multidevice_report, run_multidevice

N = 8


def _cplx(rng, n=N):
    return (rng.randn(n, n, n) + 1j * rng.randn(n, n, n)).astype(np.complex64)


# --- batching policy --------------------------------------------------------

def test_padded_size_powers_of_two():
    assert [padded_size(n, 8) for n in (1, 2, 3, 4, 5, 8)] == [1, 2, 4, 4, 8, 8]
    assert padded_size(3, 4) == 4
    with pytest.raises(ValueError):
        padded_size(0, 8)
    with pytest.raises(ValueError):
        padded_size(9, 8)


def test_stack_and_pad_zero_fills():
    rng = np.random.RandomState(0)
    arrays = [_cplx(rng) for _ in range(3)]
    batch = stack_and_pad(arrays, 4)
    assert batch.shape == (4, N, N, N)
    for i, a in enumerate(arrays):
        assert np.array_equal(batch[i], a)
    assert not batch[3].any()


def test_batcher_dispatches_on_full_or_expired():
    b = Batcher(max_batch=2, max_wait_s=10.0)
    rng = np.random.RandomState(0)
    r = lambda: TransformRequest(x=_cplx(rng))
    b.add("k1", r(), now=0.0)
    assert b.pop_ready(now=0.1) == []           # neither full nor expired
    b.add("k1", r(), now=0.2)
    ready = b.pop_ready(now=0.3)                # full
    assert [len(x) for x in ready] == [2] and b.pending == 0
    b.add("k2", r(), now=1.0)
    assert b.pop_ready(now=5.0) == []
    assert len(b.pop_ready(now=11.5)) == 1      # oldest past wait budget
    b.add("k3", r(), now=20.0)
    assert b.next_deadline(now=25.0) == 5.0     # expiry drives poll timeout


# --- request validation and bucketing ---------------------------------------

def test_request_validation():
    rng = np.random.RandomState(0)
    x = _cplx(rng)
    with pytest.raises(ValueError, match="problem"):
        TransformRequest(x=x, problem="dct")
    with pytest.raises(ValueError, match="filter h"):
        TransformRequest(x=x, problem="filtered")
    with pytest.raises(ValueError, match="forward-only"):
        TransformRequest(x=x, problem="filtered", h=x, direction="inverse")
    with pytest.raises(ValueError, match="shape="):
        # Nz is ambiguous from a half spectrum: Nh = Nz//2 + 1 is 2-to-1
        TransformRequest(x=x[:, :, :5], problem="r2c", direction="inverse")
    req = TransformRequest(x=np.abs(x).astype(np.float32), problem="r2c")
    req.validate_payload()
    bad = TransformRequest(x=x, problem="r2c")  # complex payload
    with pytest.raises(ValueError, match="must be real"):
        bad.validate_payload()
    short = TransformRequest(x=x[:, :, :5], problem="c2c")
    with pytest.raises(ValueError, match="payload shape"):
        # declared grid defaults to the payload shape; now contradict it
        short.shape = (N, N, N)
        short.validate_payload()


def test_bucket_key_separates_executables():
    """Direction and filteredness select different executables on the
    same plan — omitting either from the key would alias batches."""
    rng = np.random.RandomState(0)
    x = _cplx(rng)
    fwd = TransformRequest(x=x)
    inv = TransformRequest(x=x, direction="inverse")
    fil = TransformRequest(x=x, problem="filtered", h=x)
    keys = {bucket_key(r, "plan") for r in (fwd, inv, fil)}
    assert len(keys) == 3


# --- plan cache (meshless) --------------------------------------------------

def test_plan_cache_hits_and_lru_eviction():
    cache = PlanCache(max_plans=2)
    a = cache.get((8, 8, 8))
    assert cache.get((8, 8, 8)).plan is a.plan          # hit
    cache.get((16, 16, 16))
    cache.get((8, 8, 8))                                 # A now most recent
    cache.get((8, 8, 12))                                # evicts 16^3 (LRU)
    assert len(cache) == 2
    assert cache.stats.evictions == 1
    assert cache.key_for((16, 16, 16), np.complex64, "c2c") not in cache.keys()
    assert cache.key_for((8, 8, 8), np.complex64, "c2c") in cache.keys()
    # meshless plans are warm from birth: nothing to measure-upgrade
    assert all(cp["state"] == "warm"
               for cp in cache.snapshot()["plans"].values())


def test_plan_cache_over_capacity_does_not_livelock():
    """When every other plan is pinned by an in-flight upgrade, eviction
    must bail (temporary over-capacity) instead of spinning on the lock
    the upgrade threads need to finish."""
    cache = PlanCache(max_plans=2)
    cache.get((8, 8, 8))
    cache.get((16, 16, 16))
    for cp in cache._plans.values():
        cp.upgrading = True  # simulate in-flight measurement upgrades
    done = []

    def miss():
        cache.get((8, 8, 12))  # pre-fix: spins forever in eviction
        done.append(True)

    t = threading.Thread(target=miss, daemon=True)
    t.start()
    t.join(timeout=30.0)
    assert done, "plan-cache eviction livelocked with all plans upgrading"
    assert len(cache) == 3  # over capacity until upgrades land
    for cp in cache._plans.values():
        cp.upgrading = False
    cache.get((8, 8, 16))  # next miss drains the excess
    assert len(cache) == 2


def test_plan_cache_key_separates_problems_and_dtypes():
    cache = PlanCache()
    keys = {cache.key_for((8, 8, 8), np.complex64, "c2c"),
            cache.key_for((8, 8, 8), np.complex64, "r2c"),
            cache.key_for((8, 8, 8), np.complex128, "c2c"),
            cache.key_for((8, 8, 16), np.complex64, "c2c")}
    assert len(keys) == 4


# --- service correctness (single device) ------------------------------------

def test_service_concurrent_heterogeneous_bitwise():
    """Interleaved c2c/r2c/filtered requests from concurrent clients each
    come back bitwise-equal to the direct Croft3D call."""
    rng = np.random.RandomState(0)
    xc, h = _cplx(rng), _cplx(rng)
    xr = rng.randn(N, N, N).astype(np.float32)
    plan_c = Croft3D((N, N, N))
    plan_r = Croft3D((N, N, N), problem="r2c")
    spec_c = np.asarray(plan_c.forward(xc))
    spec_r = np.asarray(plan_r.forward(xr))
    want = {
        "c2c-fwd": (dict(problem="c2c"), xc, spec_c),
        "c2c-inv": (dict(problem="c2c", direction="inverse"), spec_c,
                    np.asarray(plan_c.inverse(spec_c))),
        "r2c-fwd": (dict(problem="r2c"), xr, spec_r),
        "r2c-inv": (dict(problem="r2c", direction="inverse",
                         shape=(N, N, N)), spec_r,
                    np.asarray(plan_r.inverse(spec_r))),
        "filtered": (dict(problem="filtered", h=h), xc,
                     np.asarray(plan_c.forward_filtered(xc, h))),
    }
    failures = []

    def client(name, reps=3):
        kw, x, ref = want[name]
        for _ in range(reps):
            got = svc.transform(x, **kw)
            if not np.array_equal(got, ref):
                failures.append((name, float(np.max(np.abs(got - ref)))))

    with TransformService(max_batch=4, max_wait_ms=2.0) as svc:
        threads = [threading.Thread(target=client, args=(name,))
                   for name in want for _ in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        stats = svc.stats()
    assert not failures, failures
    assert stats["requests"] == 2 * 3 * len(want)
    assert stats["pending"] == 0


def test_service_ragged_batch_pads_and_round_trips():
    """3 same-key requests coalesce into one dispatch padded to 4; the
    pad row never leaks into results."""
    rng = np.random.RandomState(1)
    xs = [_cplx(rng) for _ in range(3)]
    plan = Croft3D((N, N, N))
    with TransformService(max_batch=4, max_wait_ms=100.0) as svc:
        futs = [svc.submit(x) for x in xs]
        results = [f.result(timeout=120) for f in futs]
    assert all(r.ok for r in results)
    for x, r in zip(xs, results):
        assert np.array_equal(r.value, np.asarray(plan.forward(x)))
    assert {r.batch_size for r in results} == {3}
    assert {r.padded_size for r in results} == {4}


def test_service_stop_drains_pending():
    rng = np.random.RandomState(2)
    svc = TransformService(max_batch=8, max_wait_ms=5000.0)
    svc.start()
    futs = [svc.submit(_cplx(rng)) for _ in range(3)]
    svc.stop(drain=True)  # wait budget far away: stop must still serve
    assert all(f.result(timeout=60).ok for f in futs)
    with pytest.raises(RuntimeError, match="not started"):
        svc.submit(_cplx(rng))


def test_service_drain_chunks_oversized_buckets():
    """stop(drain=True) can inherit a same-key bucket larger than
    max_batch (leftover partial bucket plus late arrivals); it must chunk
    into max_batch-sized dispatches and serve every request, not fail
    them with a padded_size error."""
    import concurrent.futures
    from repro.serve.service import _Pending
    rng = np.random.RandomState(4)
    xs = [_cplx(rng) for _ in range(5)]
    ref = [np.asarray(Croft3D((N, N, N)).forward(x)) for x in xs]
    svc = TransformService(max_batch=2, max_wait_ms=5000.0)
    pendings = []
    for x in xs:  # straight to the queue, as if racing past the sentinel
        req = TransformRequest(x=x)
        req.validate_payload()
        pendings.append(_Pending(req, concurrent.futures.Future()))
        svc._queue.put(pendings[-1])
    svc._drain_all()
    results = [p.future.result(timeout=60) for p in pendings]
    assert all(r.ok for r in results), [r.error for r in results]
    assert all(r.padded_size <= 2 for r in results)
    for r, want in zip(results, ref):
        assert np.array_equal(r.value, want)


def test_service_rejects_malformed_at_submit():
    with TransformService() as svc:
        with pytest.raises(ValueError, match="rank-3"):
            svc.submit(np.zeros((4, 4), np.complex64))
        # a malformed request must not have poisoned the worker
        rng = np.random.RandomState(3)
        x = _cplx(rng)
        assert np.array_equal(svc.transform(x),
                              np.asarray(Croft3D((N, N, N)).forward(x)))


# --- wisdom: concurrent merge + stats CLI -----------------------------------

def _entry(created=None, measured=None, problem="c2c"):
    from repro.tuning.candidates import default_candidate
    cand = default_candidate((8, 8, 8), {"y": 2, "z": 2}, problem=problem)
    e = wisdom_lib.WisdomEntry.from_candidate(
        cand, source="measure" if measured else "model",
        model_s=1e-3, measured_s=measured)
    if created is not None:
        e.created = created
    return e


def test_wisdom_merge_entries_concurrent_writers(tmp_path):
    """16 threads merging disjoint keys into one file must not lose
    updates (the reload-under-lock + atomic-rename discipline)."""
    path = str(tmp_path / "w.json")
    errs = []

    def writer(i):
        try:
            wisdom_lib.merge_entries(path, {f"key{i}": _entry()})
        except Exception as e:  # pragma: no cover
            errs.append(e)

    threads = [threading.Thread(target=writer, args=(i,))
               for i in range(16)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    w = wisdom_lib.Wisdom.load(path)
    assert sorted(w.entries) == sorted(f"key{i}" for i in range(16))
    assert not os.path.exists(path + ".lock")  # lock released


def test_wisdom_merge_entries_keeps_better(tmp_path):
    path = str(tmp_path / "w.json")
    wisdom_lib.merge_entries(path, {"k": _entry(measured=2e-3)})
    wisdom_lib.merge_entries(path, {"k": _entry(measured=5e-3)})  # slower
    wisdom_lib.merge_entries(path, {"k": _entry()})               # unmeasured
    w = wisdom_lib.Wisdom.load(path)
    assert w.entries["k"].measured_s == 2e-3


def test_wisdom_stale_lock_is_broken(tmp_path):
    path = str(tmp_path / "w.json")
    lock = path + ".lock"
    with open(lock, "w") as f:
        f.write("999999")
    old = time.time() - 60.0
    os.utime(lock, (old, old))  # a writer that died a minute ago
    with wisdom_lib._FileLock(lock, timeout=1.0, stale_s=30.0):
        pass  # acquired by breaking the stale lock, not by timeout


def test_wisdom_fresh_lock_survives_break_attempt(tmp_path):
    """_break_stale must not unlink a live writer's fresh lock (the
    two-waiters-both-observe-stale race): a fresh lock is restored, a
    genuinely stale one is removed."""
    lock = str(tmp_path / "w.json.lock")
    fl = wisdom_lib._FileLock(lock, timeout=1.0, stale_s=30.0)
    with open(lock, "w") as f:
        f.write("123")  # a live holder's fresh lock
    fl._break_stale()
    assert os.path.exists(lock), "fresh lock was stolen"
    old = time.time() - 60.0
    os.utime(lock, (old, old))  # now it really is a dead writer's
    fl._break_stale()
    assert not os.path.exists(lock)
    assert not any(p.name.startswith("w.json.lock.stale")
                   for p in tmp_path.iterdir())  # no litter


def test_wisdom_stats_cli(tmp_path, capsys):
    path = str(tmp_path / "w.json")
    wisdom_lib.merge_entries(path, {
        "8x8x8|y=2,z=2|complex64|cpu": _entry(created=time.time() - 3600),
        "8x8x8|y=2,z=2|complex64|cpu|r2c": _entry(measured=1e-3,
                                                  problem="r2c"),
    })
    assert wisdom_lib._main(["stats", path]) == 0
    out = capsys.readouterr().out
    assert "2 entries" in out
    assert "measure=1" in out and "model=1" in out
    assert "c2c=1" in out and "r2c=1" in out
    assert "staleness:" in out and "1.0h old" in out


def test_wisdom_merge_cli_folds_files(tmp_path, capsys):
    a, b, out = (str(tmp_path / n) for n in ("a.json", "b.json", "out.json"))
    wisdom_lib.merge_entries(a, {"ka": _entry()})
    wisdom_lib.merge_entries(b, {"kb": _entry()})
    assert wisdom_lib._main(["merge", out, a, b]) == 0
    assert sorted(wisdom_lib.Wisdom.load(out).entries) == ["ka", "kb"]


# --- distributed service: one subprocess, the full lifecycle ----------------

_MULTIDEVICE_CODE = """
import json, os, tempfile, time
import numpy as np, jax
from repro.serve import TransformService, PlanCache
from repro.launch.mesh import make_mesh

mesh = make_mesh((2, 4), ("y", "z"))
wisdom = os.path.join(tempfile.mkdtemp(), "w.json")
cache = PlanCache(mesh, wisdom_path=wisdom, max_plans=2, measure_after=3,
                  upgrade_async=False, tune_kw=dict(top_k=2, measure_iters=1))
svc = TransformService(mesh, max_batch=4, max_wait_ms=30.0, cache=cache)
rng = np.random.RandomState(0)
N = 16
xc = (rng.randn(N, N, N) + 1j * rng.randn(N, N, N)).astype(np.complex64)
xr = rng.randn(N, N, N).astype(np.float32)

with svc:
    # heterogeneous concurrent batch: 3 c2c (ragged -> padded 4) + 1 r2c
    futs = [svc.submit(xc) for _ in range(3)] + [svc.submit(xr, problem="r2c")]
    results = [f.result(timeout=400) for f in futs]
    assert all(r.ok for r in results), [r.error for r in results]
    assert results[0].batch_size == 3 and results[0].padded_size == 4

    # bitwise equality against direct calls on the same cached plans
    plan_c = cache.get((N, N, N), np.complex64, "c2c").plan
    ref = np.asarray(plan_c.forward(
        jax.device_put(xc, plan_c.input_sharding)))
    for r in results[:3]:
        assert np.array_equal(r.value, ref)
    plan_r = cache.get((N, N, N), np.complex64, "r2c").plan
    ref_r = np.asarray(plan_r.forward(jax.device_put(
        xr.astype(plan_r.input_dtype), plan_r.input_sharding)))
    assert np.array_equal(results[3].value, ref_r)

    # cold -> warm: measure_after=3 dispatches arms the (synchronous
    # here) measurement upgrade; later dispatches ride the measured plan
    states = [svc.submit(xc).result(timeout=400).plan_state
              for _ in range(3)]
    assert states[-1] == "warm", states
    assert cache.stats.upgrades == 1

    # the measured winner was merged into the wisdom store atomically
    blob = json.load(open(wisdom))
    measured = [k for k, e in blob["entries"].items()
                if e["source"] == "measure"]
    assert measured, blob["entries"].keys()
    assert not os.path.exists(wisdom + ".lock")

    # LRU eviction under shape diversity: a third key exceeds max_plans=2
    assert svc.submit((rng.randn(8, 8, 8) + 0j).astype(np.complex64)
                      ).result(timeout=400).ok
    assert len(cache) == 2 and cache.stats.evictions >= 1

print("SERVE_MULTIDEVICE_OK")
"""


def test_service_multidevice_lifecycle():
    out = run_multidevice(_MULTIDEVICE_CODE, n_devices=8, timeout=480)
    assert "SERVE_MULTIDEVICE_OK" in out


# --- batched dispatch amortizes collectives ---------------------------------

_BATCHED_COLLECTIVES_CODE = """
import json, os, tempfile
import numpy as np, jax
from repro.core import Croft3D, Decomposition
from repro.launch import hlo_cost
from repro.launch.mesh import make_mesh
from repro.serve import PlanCache

mesh = make_mesh((2, 4), ("y", "z"))
cache = PlanCache(mesh, wisdom_path=os.path.join(tempfile.mkdtemp(), "w.json"))
plans = {
    # the plan the service itself would pick for a c2c request
    "c2c": cache.get((32, 32, 32), np.complex64, "c2c").plan,
    # packed r2c: its batched path is the native leading-batch pipeline
    "r2c": Croft3D((32, 32, 32), mesh, Decomposition("pencil", ("y", "z")),
                   problem="r2c", strategy="packed"),
}

def collectives(lowered):
    return hlo_cost.analyze(lowered.compile().as_text()).collectives

report = {}
for name, plan in plans.items():
    report[name] = {"single": collectives(plan.lower_forward())}
    for b in (1, 4):
        spec = jax.ShapeDtypeStruct((b,) + plan.shape, plan.input_dtype,
                                    sharding=plan.batched_sharding("input"))
        report[name][b] = collectives(plan._batched_fn("forward").lower(spec))
print("REPORT " + json.dumps(report))
"""


@pytest.fixture(scope="module")
def batched_collectives():
    return multidevice_report(_BATCHED_COLLECTIVES_CODE)


@pytest.mark.parametrize("problem", ["c2c", "r2c"])
@pytest.mark.parametrize("batch", [1, 4])
def test_batched_forward_compiles_to_single_request_collectives(
        batched_collectives, problem, batch):
    """A (B, ...) stacked forward compiles to exactly the single
    request's collective ops, each moving B times the bytes: batching
    amortizes launches, it never adds one."""
    rec = batched_collectives[problem]
    single, got = rec["single"], rec[str(batch)]
    assert single and set(got) == set(single), (single, got)
    for kind, op in single.items():
        assert got[kind]["count"] == op["count"], (kind, single, got)
        assert got[kind]["bytes"] == batch * op["bytes"], (kind, single, got)
