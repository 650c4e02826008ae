"""The reduction from a profiler trace to the per-layer metrics: on
hand-built events with known overlaps, and on a small trace recorded here
on the CPU."""

import pytest

from bench import trace_reduce as tr
from bench.trace_reduce import Op, Span

TPU_FUSION = ("%fusion.59 = (f32[128,64,8,16]{0,3,2,1:T(8,128)S(1)}, "
              "f32[128]{0}) fusion(f32[8,16]{1,0} %copy-done.7), "
              "kind=kOutput")
TPU_A2A = ("%all_to_all.203 = c64[512,1024,256]{2,1,0:T(8,128)} "
           "all-to-all(c64[512,1024,256]{2,1,0} %fusion.2), "
           "replica_groups={{0,1},{2,3}}")
TPU_CONSUMER = "%fusion.9 = f32[4]{0} fusion(c64[4] %all_to_all.3), kind=kLoop"
TPU_PALLAS = ('%custom-call.7 = f32[8,128]{1,0} custom-call(f32[8,128]{1,0} '
              '%a), custom_call_target="tpu_custom_call"')
TPU_COMBINE = ('%custom-call.2 = c64[128,128,65]{1,0,2:T(8,128)} custom-call('
               'f32[128,128,65]{1,0,2} %copy.57), '
               'custom_call_target="X64Combine"')


@pytest.mark.parametrize("text,category,label", [
    (TPU_FUSION, "fft", "fusion.59 fusion"),
    (TPU_A2A, "collective", "all_to_all.203 all-to-all"),
    (TPU_CONSUMER, "fft", "fusion.9 fusion"),
    (TPU_PALLAS, "pallas", "custom-call.7 custom-call:tpu_custom_call"),
    (TPU_COMBINE, "fft", "custom-call.2 custom-call:X64Combine"),
    ("%collective-permute-start.1 = (f32[4], f32[4]) "
     "collective-permute-start(f32[4] %x)", "collective",
     "collective-permute-start.1 collective-permute-start"),
    ("dot.1", "fft", "dot.1 dot"),
    ("all-to-all.2", "collective", "all-to-all.2 all-to-all"),
])
def test_categories_and_labels(text, category, label):
    assert tr.category(text) == category
    assert tr.label(text) == label


def test_interval_arithmetic():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert tr.subtract([(0, 10)], [(2, 3), (5, 6)]) == [(0, 2), (3, 5),
                                                        (6, 10)]
    assert tr.subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]
    assert tr.clip([(0, 5), (8, 12)], 2, 10) == [(2, 5), (8, 10)]
    assert tr.gaps([(2, 3)], 0, 5) == [(0, 2), (3, 5)]


def _known_trace():
    """Two devices over a 100 ns window.  dev0: fft 10-30, collective
    25-45 (5 ns under the fft), pallas 50-60, idle 0-10, 45-50, 60-100.
    dev1: one fft op 0-100 and a collective 40-50 entirely under it."""
    ops = [Op("d0", "f", 10, 30, "fft"), Op("d0", "a", 25, 45, "collective"),
           Op("d0", "p", 50, 60, "pallas"),
           Op("d1", "g", 0, 100, "fft"), Op("d1", "b", 40, 50, "collective")]
    spans = [Span("bench.window", 0, 100), Span("bench.step", 0, 100),
             Span("bench.call.forward", 0, 8), Span("bench.wait", 44, 100)]
    return ops, spans


def test_reduce_known_overlaps():
    ops, spans = _known_trace()
    r = tr.reduce(ops, spans)
    assert r.window == (0, 100)
    d0, d1 = r.devices["d0"], r.devices["d1"]
    assert d0.category_ns == {"fft": 20, "collective": 20, "pallas": 10}
    assert d0.busy_ns == 45                      # 10-45 and 50-60
    assert d0.exposed_collective_ns == 15        # 30-45
    assert d1.busy_ns == 100
    assert d1.exposed_collective_ns == 0
    assert r.mean("busy_ns") == 72.5
    assert r.mean("category_ns", "collective") == 15
    # idle on d0: 0-10 (mid 5: forward call), 45-50 and 60-100 (wait)
    assert r.idle_by_span == {"bench.call.forward": 5.0, "bench.wait": 22.5}
    assert r.top_gaps(1) == [["bench.wait", 22.5e-9]]
    assert r.span_ns("bench.call.") == 8
    assert r.has_category("pallas") and not r.has_category("other")
    names = [name for name, _ in r.top_ops(3)]
    assert names[0] == "g"


def test_window_clips_ops_and_defaults_to_extent():
    ops = [Op("d", "x", -10, 10, "fft"), Op("d", "y", 90, 120, "collective")]
    r = tr.reduce(ops, [Span("bench.window", 0, 100)])
    assert r.devices["d"].busy_ns == 20
    assert r.devices["d"].category_ns["collective"] == 10
    assert tr.reduce(ops, []).window == (-10, 120)


def test_metric_readers_on_known_trace(bm):
    from bench import harness
    ops, spans = _known_trace()
    ctx = harness.MetricContext(trace=tr.reduce(ops, spans), steps=1,
                                window_s=100e-9, least_hbm_bytes=819,
                                peaks={"hbm_bytes_per_s": 819e9})
    read = {m["name"]: harness.metric_reader(m["name"]).read(ctx)
            for m in bm["per_layer"]}
    assert read["device_idle_frac"] == pytest.approx(1 - 72.5 / 100)
    assert read["collective_ms"] == pytest.approx(15e-6)
    assert read["collective_exposed_ms"] == pytest.approx(7.5e-6)
    assert read["fft_compute_ms"] == pytest.approx(60e-6)
    assert read["pallas_ms"] == pytest.approx(5e-6)
    assert read["dispatch_ms"] == pytest.approx(8e-6)
    # 819 B at 819 GB/s is 1 ns of a 100 ns step
    assert read["hbm_floor_frac"] == pytest.approx(0.01)


def test_readers_return_nothing_without_their_ops(bm):
    from bench import harness
    ops = [Op("d", "x", 0, 10, "fft")]
    ctx = harness.MetricContext(trace=tr.reduce(ops, []), steps=2,
                                window_s=1e-8, least_hbm_bytes=1, peaks=None)
    for name in ("collective_ms", "collective_exposed_ms", "pallas_ms",
                 "dispatch_ms", "hbm_floor_frac"):
        assert harness.metric_reader(name).read(ctx) is None, name
    empty = harness.MetricContext(trace=None, steps=0, window_s=0.0,
                                  least_hbm_bytes=1, peaks=None)
    for m in bm["per_layer"]:
        assert harness.metric_reader(m["name"]).read(empty) is None


def test_recorded_cpu_trace(tmp_path):
    """A trace recorded here: the benchmark's spans are found, the CPU's
    XLA ops stand in for a device, and the busy time fits the window."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    f = jax.jit(lambda x: jnp.fft.fft(x) @ x)
    x = jnp.ones((128, 128), jnp.complex64)
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        with TraceAnnotation("bench.window"):
            for _ in range(3):
                with TraceAnnotation("bench.step"):
                    with TraceAnnotation("bench.call.forward"):
                        y = f(x)
                    with TraceAnnotation("bench.wait"):
                        y.block_until_ready()
    ops, spans = tr.load_xplane(tr.find_xplane(str(tmp_path)))
    names = [s.name for s in spans]
    assert names.count("bench.step") == 3 and "bench.window" in names
    assert ops and all(o.device.startswith("cpu:") for o in ops)
    r = tr.reduce(ops, spans)
    assert 0 < r.mean("busy_ns") <= r.window_ns
    assert r.has_category("fft") and not r.has_category("collective")
    assert r.span_ns("bench.call.") > 0
