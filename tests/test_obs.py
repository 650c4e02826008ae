"""repro.obs: tracer, metrics registry, instrumentation guarantees.

The three load-bearing claims of the observability subsystem:

  * the tracer is safe under concurrent emission (the serve worker,
    plan-cache upgrade threads, and clients share one ring buffer);
  * histogram quantiles are honest (pinned against numpy within the
    log-bucket growth factor; exact for explicit-bounds histograms);
  * instrumentation is zero-cost when disabled — enabling the tracer
    and its profiler sink must not change compiled HLO (pinned
    byte-for-byte in an 8-virtual-device subprocess).
"""

import json
import math
import threading
import time

import numpy as np
import pytest

from repro import obs
from repro.obs import tracer as tracer_lib
from repro.serve import TransformService
from conftest import run_multidevice


@pytest.fixture
def tracer():
    """A recording tracer installed globally, restored afterwards."""
    prev = obs.get_tracer()
    tr = tracer_lib.Tracer()
    obs.set_tracer(tr)
    yield tr
    obs.set_tracer(prev)


# --- tracer -----------------------------------------------------------------

def test_noop_tracer_is_default_and_allocation_free():
    tr = obs.get_tracer()
    assert tr is obs.NOOP and not tr.enabled
    # one shared null context manager: no per-span allocation when disabled
    assert tr.span("a", "fft") is tr.span("b", "collective")
    assert tr.events() == []
    tr.instant("x")
    tr.complete("x", "fft", 0.0, 1.0)
    assert tr.events() == []


def test_span_nesting_and_error_capture(tracer):
    with tracer.span("outer", "plan", plan="p"):
        with tracer.span("inner", "fft") as sp:
            sp.set(chunk=3)
    with pytest.raises(ValueError):
        with tracer.span("boom", "collective"):
            raise ValueError("nope")
    evs = {e["name"]: e for e in tracer.events()}
    # inner closed before outer; both are complete events with args
    assert set(evs) == {"outer", "inner", "boom"}
    assert all(e["ph"] == "X" and e["dur"] >= 0 for e in evs.values())
    assert evs["inner"]["args"]["chunk"] == 3
    assert evs["outer"]["args"]["plan"] == "p"
    assert evs["boom"]["args"]["error"] == "ValueError"


def test_tag_scope_nests_and_restores(tracer):
    with obs.tag_scope(traffic="tuning"):
        with obs.tag_scope(plan="slab"):
            tracer.instant("in2", "plan")
        tracer.instant("in1", "plan")
    tracer.instant("out", "plan")
    evs = {e["name"]: e["args"] for e in tracer.events()}
    assert evs["in2"] == {"traffic": "tuning", "plan": "slab"}
    assert evs["in1"] == {"traffic": "tuning"}
    assert evs["out"] == {}


def test_tracer_thread_safety_under_concurrent_emission():
    """Worker + upgrade-thread shape: N threads race spans, instants, and
    retroactive completes into one tracer; every event lands, the buffer
    stays consistent."""
    tr = tracer_lib.Tracer(capacity=100_000)
    n_threads, n_each = 8, 200
    barrier = threading.Barrier(n_threads)

    def emitter(tid):
        barrier.wait()
        for i in range(n_each):
            with tr.span(f"t{tid}", "fft", i=i):
                pass
            tr.instant(f"t{tid}:i", "queue")
            t0 = time.monotonic()
            tr.complete(f"t{tid}:c", "collective", t0, t0 + 1e-4)

    threads = [threading.Thread(target=emitter, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    evs = tr.events()
    assert len(evs) == n_threads * n_each * 3
    assert tr.dropped == 0
    per_thread = {}
    for e in evs:
        assert e["ph"] in ("X", "i") and e["ts"] >= 0
        per_thread[e["name"]] = per_thread.get(e["name"], 0) + 1
    for t in range(n_threads):
        assert per_thread[f"t{t}"] == n_each


def test_ring_buffer_caps_memory_and_counts_drops():
    tr = tracer_lib.Tracer(capacity=16)
    for i in range(40):
        tr.instant(f"e{i}", "plan")
    evs = tr.events()
    assert len(evs) == 16
    assert tr.dropped == 24
    assert [e["name"] for e in evs] == [f"e{i}" for i in range(24, 40)]
    assert tr.to_chrome()["metadata"]["dropped_events"] == 24


def test_chrome_trace_save_round_trip(tmp_path, tracer):
    with tracer.span("s", "fft", k=2):
        tracer.instant("i", "queue")
    tracer.add_meta("attribution", [{"plan": "p"}])
    path = tmp_path / "trace.json"
    tracer.save(str(path))
    doc = json.loads(path.read_text())
    assert set(doc) == {"traceEvents", "displayTimeUnit", "metadata"}
    assert doc["metadata"]["attribution"] == [{"plan": "p"}]
    for ev in doc["traceEvents"]:
        assert {"name", "cat", "ph", "pid", "tid", "ts"} <= set(ev)
        assert ev["cat"] in obs.CATEGORIES


def test_tracing_contextmanager_scopes_and_saves(tmp_path):
    path = tmp_path / "t.json"
    before = obs.get_tracer()
    with obs.tracing(str(path)) as tr:
        assert obs.get_tracer() is tr
        tr.instant("hello", "plan")
    assert obs.get_tracer() is before
    assert json.loads(path.read_text())["traceEvents"][0]["name"] == "hello"


# --- metrics ----------------------------------------------------------------

def test_counter_and_gauge_basics():
    reg = obs.MetricsRegistry()
    c = reg.counter("reqs", "requests")
    c.inc()
    c.inc(4)
    assert c.value == 5
    with pytest.raises(ValueError):
        c.inc(-1)
    g = reg.gauge("depth")
    g.set(3)
    g.inc()
    g.dec(2)
    assert g.value == 2
    # get-or-create returns the same object; kind mismatch is loud
    assert reg.counter("reqs") is c
    with pytest.raises(TypeError):
        reg.gauge("reqs")
    with pytest.raises(TypeError):
        reg.histogram("depth")


@pytest.mark.parametrize("dist", ["uniform", "lognormal"])
def test_histogram_quantiles_vs_numpy(dist):
    """Log-bucketed quantile estimates stay within one bucket growth
    factor of numpy's exact quantiles."""
    rng = np.random.RandomState(0)
    if dist == "uniform":
        xs = rng.uniform(1e-4, 1e-1, size=5000)
    else:
        xs = np.exp(rng.normal(loc=-6.0, scale=1.5, size=5000))
    growth = 1.4
    h = obs.Histogram("lat", growth=growth)
    for x in xs:
        h.observe(x)
    assert h.count == len(xs)
    assert math.isclose(h.sum, float(xs.sum()), rel_tol=1e-9)
    for q in (0.05, 0.25, 0.50, 0.90, 0.99):
        exact = float(np.quantile(xs, q))
        est = h.quantile(q)
        assert exact / growth <= est <= exact * growth, (
            f"{dist} q{q}: est {est} vs numpy {exact}")
    # clamped to observed extremes
    assert h.quantile(0.0) >= float(xs.min())
    assert h.quantile(1.0) <= float(xs.max())


def test_histogram_explicit_bounds_exact():
    h = obs.Histogram("batch", bounds=range(1, 9))
    for v, n in ((1, 3), (4, 2), (8, 1)):
        for _ in range(n):
            h.observe(v)
    # cumulative buckets diff back to the exact integer histogram
    per_size, prev = {}, 0
    for edge, cum in h.buckets()[:-1]:
        if cum > prev:
            per_size[int(edge)] = cum - prev
        prev = cum
    assert per_size == {1: 3, 4: 2, 8: 1}
    # a single-valued distribution reports that value at every quantile
    h1 = obs.Histogram("one", bounds=range(1, 9))
    for _ in range(10):
        h1.observe(4)
    assert h1.quantile(0.5) == 4 == h1.quantile(0.99)


def test_histogram_empty_and_overflow():
    h = obs.Histogram("x", bounds=[1.0, 2.0])
    assert h.quantile(0.5) is None
    h.observe(5.0)  # beyond the last edge -> +Inf bucket
    assert h.buckets()[-1] == (math.inf, 1)
    assert h.quantile(0.5) == 5.0  # clamped to observed max
    assert h.snapshot()["buckets"] == {"+Inf": 1}


def test_prometheus_exposition_format():
    reg = obs.MetricsRegistry()
    reg.counter("serve_requests", "served").inc(3)
    reg.gauge("queue-depth").set(2)  # name sanitized for prometheus
    h = reg.histogram("lat_s", bounds=[0.1, 1.0])
    h.observe(0.05)
    h.observe(0.5)
    text = reg.to_prometheus()
    assert "# TYPE serve_requests counter" in text
    assert "serve_requests 3" in text
    assert "queue_depth 2" in text
    lines = [ln for ln in text.splitlines() if ln.startswith("lat_s_bucket")]
    assert lines == ['lat_s_bucket{le="0.1"} 1', 'lat_s_bucket{le="1"} 2',
                     'lat_s_bucket{le="+Inf"} 2']
    assert "lat_s_count 2" in text
    # snapshot is JSON-able and mirrors the same objects
    snap = reg.snapshot()
    json.dumps(snap)
    assert snap["serve_requests"]["value"] == 3
    assert snap["lat_s"]["count"] == 2


# --- serve lifecycle --------------------------------------------------------

def test_serve_lifecycle_ordering_ragged_batch(tracer):
    """3 coalesced requests pad to 4: every result's timestamps satisfy
    submit <= dispatch <= resolve, lifecycle spans land in the trace,
    and the padding-waste counter sees the ragged batch's dead row."""
    rng = np.random.RandomState(0)
    xs = [(rng.randn(8, 8, 8) + 1j * rng.randn(8, 8, 8)).astype(np.complex64)
          for _ in range(3)]
    with TransformService(max_batch=4, max_wait_ms=100.0) as svc:
        futs = [svc.submit(x) for x in xs]
        results = [f.result(timeout=120) for f in futs]
        stats = svc.stats()
    assert all(r.ok for r in results)
    for r in results:
        assert 0.0 < r.t_submit <= r.t_dispatch <= r.t_done
        assert math.isclose(r.latency_s, r.t_done - r.t_submit, rel_tol=1e-6)

    # registry is the source of truth; stats() is the compat view over it
    reg = svc.registry
    assert reg.counter("serve_requests").value == 3
    real = reg.counter("serve_real_rows").value
    padded = reg.counter("serve_padded_rows").value
    waste = reg.counter("serve_padding_waste_rows").value
    assert waste == padded - real > 0  # 3 rows padded to 4: one dead slot
    assert stats["requests"] == 3
    assert stats["padding_waste_rows"] == waste
    assert sum(stats["batch_hist"].values()) == stats["batches"]
    assert sum(k * v for k, v in stats["batch_hist"].items()) == real
    assert stats["latency_ms"]["p50"] is not None
    prom = reg.to_prometheus()
    assert "serve_requests 3" in prom

    # lifecycle spans: per request, the queue span runs from submit to
    # dispatch on one monotonic clock
    evs = tracer.events()
    by_name = {}
    for e in evs:
        by_name.setdefault(e["name"], []).append(e)
    assert len(by_name["request:submit"]) == 3
    assert len(by_name["request:queue"]) == 3
    assert by_name["batch:dispatch"] and by_name["batch:compute"]
    assert by_name["batch:h2d"] and by_name["batch:d2h"]
    submit_ts = {e["args"]["req_id"]: e["ts"]
                 for e in by_name["request:submit"]}
    dispatch_end = max(d["ts"] + d["dur"] for d in by_name["batch:dispatch"])
    for q in by_name["request:queue"]:
        rid = q["args"]["req_id"]
        # queue span starts at submit (the submit instant fires just
        # after the enqueue) and ends before the dispatch span closes
        assert q["ts"] <= submit_ts[rid] + 1e4  # within 10ms bookkeeping
        assert q["ts"] + q["dur"] <= dispatch_end
        assert q["args"]["reason"] in ("full", "deadline", "drain")
    assert {d["args"]["n"] for d in by_name["batch:dispatch"]} == {3}


def test_service_stats_shape_unchanged_without_tracing():
    """The compat dict keeps its pre-obs keys with the noop tracer (the
    default): existing callers and benches keep working."""
    rng = np.random.RandomState(1)
    x = (rng.randn(8, 8, 8) + 1j * rng.randn(8, 8, 8)).astype(np.complex64)
    with TransformService(max_batch=2, max_wait_ms=2.0) as svc:
        assert svc.transform(x).shape == (8, 8, 8)
        stats = svc.stats()
    assert {"requests", "batches", "mean_batch", "real_rows", "padded_rows",
            "padding_waste_rows", "occupancy", "batch_hist", "pending",
            "latency_ms", "plan_cache"} <= set(stats)
    assert stats["requests"] == 1 and stats["pending"] == 0


# --- profiler sink ----------------------------------------------------------

def test_profiler_sink_swaps_the_noop_tracer_and_restores_it():
    assert obs.get_tracer() is obs.NOOP
    with obs.profiler_sink():
        tr = obs.get_tracer()
        assert tr is tracer_lib.PROFILER_SPANS and not tr.enabled
        with tr.span("croft.forward", "plan", rows=2):
            pass
        assert tr.events() == []
        obs.disable()                      # the sink outlives disable()
        assert obs.get_tracer() is tracer_lib.PROFILER_SPANS
    assert obs.get_tracer() is obs.NOOP


def test_recording_tracer_under_the_sink_still_records(tracer):
    with obs.profiler_sink():
        with tracer.span("outer", "plan", k=1) as sp:
            sp.set(done=True)
    with tracer.span("after", "plan"):
        pass
    evs = {e["name"]: e for e in tracer.events()}
    assert set(evs) == {"outer", "after"}
    assert evs["outer"]["args"] == {"k": 1, "done": True}


def test_croft_entries_open_spans(tracer):
    from repro.core import Croft3D
    plan = Croft3D((8, 8, 8), problem="r2c", strategy="packed")
    x = np.ones((8, 8, 8), np.float32)
    h = np.ones(plan.spectrum_shape, np.complex64)
    plan.forward(x)
    plan.inverse(plan.forward_filtered(x, h))
    plan.forward_batched(np.stack([x, x]))
    names = [e["name"] for e in tracer.events()]
    assert names == ["croft.forward", "croft.forward_filtered",
                     "croft.inverse", "croft.forward_batched"]


# --- zero-cost (8 virtual devices) ------------------------------------------

def test_hlo_identical_with_tracer_and_profiler_sink():
    """The acceptance pin: a recording tracer with the profiler sink on
    changes NOTHING in the compiled HLO (byte-identical), the traced
    calls return the production output, and every entry opened its
    span."""
    run_multidevice("""
import numpy as np, jax, jax.numpy as jnp
from repro import obs
from repro.core import Croft3D, Decomposition, FFTOptions
from repro.tuning.measure import _random_input
from repro.launch.mesh import make_mesh

mesh = make_mesh((2, 4), ("y", "z"))
N = 16
plans = {
    "alltoall-k2": Croft3D((N, N, N), mesh, Decomposition("pencil", ("y", "z")),
                           FFTOptions(overlap_k=2, transpose_impl="alltoall",
                                      output_layout="spectral")),
    "ring-k1": Croft3D((N, N, N), mesh, Decomposition("pencil", ("y", "z")),
                       FFTOptions(overlap_k=1, transpose_impl="ring",
                                  output_layout="spectral")),
}
entries = ("forward", "inverse", "forward_filtered")

def texts():
    return {(k, e): p.lower(e).compile().as_text()
            for k, p in plans.items() for e in entries}

hlo_off = texts()
xs = {k: _random_input((N, N, N), jnp.complex64, p.input_sharding)
      for k, p in plans.items()}
want = {k: np.asarray(jax.device_get(p.forward(xs[k])))
        for k, p in plans.items()}
with obs.tracing() as tracer, obs.profiler_sink():
    with jax.profiler.TraceAnnotation("outer"):
        got = {k: np.asarray(jax.device_get(p.forward(xs[k])))
               for k, p in plans.items()}
    hlo_on = texts()
for key in hlo_off:
    assert hlo_on[key] == hlo_off[key], (
        str(key) + ": tracing changed the compiled HLO")
for k in plans:
    np.testing.assert_array_equal(got[k], want[k])
assert [e["name"] for e in tracer.events()] == ["croft.forward"] * 2
assert obs.get_tracer() is obs.NOOP
print("OK")
""", n_devices=8)
