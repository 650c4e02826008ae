"""The split of a trace by the program's own names (bench/trace_scopes.py):
roles, stages and program runs on hand-built events, the op-name map of
a compiled HLO text, and a trace of ``Croft3D`` recorded here on the CPU
with the program's spans on the profiler's clock."""

import dataclasses

import pytest

from bench import trace_reduce as tr
from bench import trace_scopes as ts
from bench.trace_reduce import Op, Span
from bench.trace_scopes import ScopedOp

DFT = "jit(croft_forward)/croft.stage.x-fft+xy/k0/croft.dft/dot_general"


@pytest.mark.parametrize("scope,role,place,key", [
    (DFT, "croft.dft", "croft.stage.x-fft+xy/k0",
     "croft.stage.x-fft+xy/k0/croft.dft"),
    ("jit(croft_inverse)/croft.scale/mul", "croft.scale", "", "croft.scale"),
    ("jit(a)/croft.stage.x-fft/croft.relayout/transpose;"
     "jit(a)/croft.stage.y-fft/croft.relayout/transpose", "croft.relayout",
     "croft.stage.x-fft;croft.stage.y-fft",
     "croft.stage.x-fft;croft.stage.y-fft/croft.relayout"),
    ("croft.stage.z-fft/(xla)", ts.XLA_TAG, "croft.stage.z-fft",
     "croft.stage.z-fft/(xla)"),
    ("croft.stage.restore-xy/k1/(xla)", ts.XLA_TAG,
     "croft.stage.restore-xy/k1", "croft.stage.restore-xy/k1/(xla)"),
    ("jit(croft_forward)/shard_map", ts.UNSCOPED, "", ts.UNSCOPED),
    ("", ts.UNSCOPED, "", ts.UNSCOPED),
])
def test_roles_stages_and_keys(scope, role, place, key):
    assert ts.role_of(scope) == role
    assert ts.place_of(scope) == place
    assert ts.scope_key(scope) == key


HLO = """HloModule jit_croft_forward, entry_computation_layout={(c64[4]{0})->c64[4]{0}}

%fused_computation.1 (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  ROOT %copy.3 = f32[4]{0:T(8,128)} copy(f32[4]{0} %param_0)
}

%fused_computation.2 (param_0.1: f32[4]) -> f32[4] {
  %param_0.1 = f32[4]{0} parameter(0)
  ROOT %exponential.1 = f32[4]{0} exponential(f32[4]{0} %param_0.1)
}

ENTRY %main.0_spmd (x: c64[4]) -> c64[4] {
  %x = c64[4]{0} parameter(0), metadata={op_name="x"}
  %v.0 = f32[4]{0} custom-call(c64[4]{0} %x), custom_call_target="X64SplitLow", metadata={op_name="v"}
  %dot.1 = f32[4]{0} dot(f32[4]{0} %v.0, f32[4]{0} %v.0), metadata={op_name="DFT_SCOPE"}
  %copy_fusion = f32[4]{0} fusion(f32[4]{0} %dot.1), kind=kLoop, calls=%fused_computation.1
  %exp_fusion = (f32[4]{0}, f32[4]{0}) fusion(f32[4]{0} %copy_fusion), kind=kLoop, calls=%fused_computation.2
  ROOT %shard_map.5 = c64[4]{0} custom-call(f32[4]{0} %exp_fusion, f32[4]{0} %exp_fusion), custom_call_target="X64Combine", metadata={op_name="jit(croft_forward)/shard_map"}
}
""".replace("DFT_SCOPE", DFT)


def test_op_names_keeps_scopes_and_names_what_xla_moves():
    names = ts.op_names(HLO)
    xla = "croft.stage.x-fft+xy/k0/(xla)"
    assert names["dot.1"] == DFT
    # XLA's own data movement: a split, a fusion of copies, a combine at
    # the shard_map's edge; the stage and chunk are those of the nearest
    # scoped op, the role none of the program's
    assert names["v.0"] == xla
    assert ts.role_of(xla) == ts.XLA_TAG
    assert names["copy_fusion"] == xla
    assert names["shard_map.5"] == xla
    # an unnamed fusion that computes stays unscoped
    assert ts.role_of(names.get("exp_fusion", "")) == ts.UNSCOPED
    assert ts.module_name("jit_croft_forward(1382362310797362971)") == \
        "jit_croft_forward"


def _scoped_trace():
    """Two devices over a 100 ns window.  d0: program a (run 1) runs a DFT
    at 10-30 and a relayout at 35-45; program b (run 2) a scale at 60-70,
    a transpose (a collective) at 70-80 and an unscoped op at 85-90.
    d1: program a (run 1) runs a relayout at 0-50 and a relayout that
    XLA made a collective at 50-100."""
    def op(dev, name, s, e, cat, scope, prog, run):
        return ScopedOp(dev, name, s, e, cat, scope, prog, run)
    stage = "jit(a)/croft.stage.s/"
    ops = [op("d0", "f", 10, 30, "fft", stage + "croft.dft/dot", "a", 1),
           op("d0", "r", 35, 45, "fft", stage + "croft.relayout/t", "a", 1),
           op("d0", "s", 60, 70, "pallas", "jit(b)/croft.scale/mul", "b", 2),
           op("d0", "c", 70, 80, "collective",
              stage + "croft.transpose/all_to_all", "b", 2),
           op("d0", "u", 85, 90, "fft", "", "b", 2),
           op("d1", "r1", 0, 50, "fft", stage + "croft.relayout/t", "a", 1),
           op("d1", "r2", 50, 100, "collective", stage + "croft.relayout/rev",
              "a", 1)]
    spans = [Span("bench.window", 0, 100), Span("bench.wait", 40, 100)]
    return ops, spans


def test_reduce_roles_and_idle_split():
    ops, spans = _scoped_trace()
    croft = [Span("croft.forward", 1, 5), Span("croft.inverse", 200, 300)]
    runs = {"d0": [(5, 50, "a", 1), (55, 95, "b", 2)]}
    red = ts.reduce(ops, spans, croft, runs=runs)
    assert red.role_ns["d0"] == {"croft.dft": 20, "croft.relayout": 10,
                                 "croft.scale": 10, "croft.transpose": 10,
                                 ts.UNSCOPED: 5}
    # the relayout that XLA made a collective is not relayout time
    assert red.role_ns["d1"] == {"croft.relayout": 50}
    # d0 idles 0-10 and 90-100 at the window's edges, 30-35 and 80-85
    # inside a run, 45-60 between runs; d1 never
    assert (red.idle_edge_ns, red.idle_in_program_ns, red.idle_between_ns) \
        == ({"d0": 20, "d1": 0}, {"d0": 10, "d1": 0}, {"d0": 15, "d1": 0})
    assert red.idle_pairs == {"a -> b": 7.5}
    # of d0's 45-60 between runs, 45-50 and 55-60 lie in a module's span
    assert red.idle_between_in_module_ns == {"d0": 10, "d1": 0}
    assert red.idle_in_by_role == {"croft.dft": 2.5, "croft.transpose": 2.5}
    assert red.program_ns == {"a": 65.0, "b": 12.5}
    assert red.scope_ns["croft.stage.s/croft.relayout"] == 55.0
    assert [s.name for s in red.croft_spans] == ["croft.forward"]
    m = ts.metrics(red, steps=1)
    assert m == pytest.approx({
        "dft_ms": 10e-6, "relayout_ms": 30e-6, "idle_in_program_ms": 5e-6,
        "idle_between_programs_ms": 7.5e-6, "unscoped_ms": 2.5e-6,
        "xla_moves_ms": 0})
    b = ts.breakdown(red, steps=1)
    assert b["scopes"][0] == ["croft.stage.s/croft.relayout", 55e-6]
    assert b["scopes"][-1] == [ts.UNSCOPED, 2.5e-6]
    assert b["idle_boundaries"]["between_programs"] == [["a -> b", 7.5e-6]]


def test_existing_metrics_read_the_same_from_scoped_ops(bm):
    """The seven per-layer metrics and the two breakdowns of
    ``trace_reduce`` read the same from scoped ops as from the same ops
    without their scope and program."""
    from bench import harness
    ops, spans = _scoped_trace()
    plain = [Op(o.device, o.name, o.start, o.end, o.category) for o in ops]
    base = ts.reduce(ops, spans).base

    def read(red):
        ctx = harness.MetricContext(trace=red, steps=2, window_s=100e-9,
                                    least_hbm_bytes=819,
                                    peaks={"hbm_bytes_per_s": 819e9})
        return ({m["name"]: harness.metric_reader(m["name"]).read(ctx)
                 for m in bm["per_layer"]},
                red.top_ops(10), red.top_gaps(10))
    want = tr.reduce(plain, spans)
    assert read(base) == read(want)
    assert dataclasses.asdict(base.devices["d0"]) == \
        dataclasses.asdict(want.devices["d0"])
    assert len(read(want)[0]) == 7


def test_recorded_cpu_trace_of_croft3d(tmp_path):
    """A trace recorded here under the span sink: ``croft.forward`` lies
    inside ``bench.call.forward``; every op is found in its program's
    compiled text and has a role; each call is a program run of its own."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.profiler import TraceAnnotation
    from repro.core import Croft3D
    from repro.obs import tracer

    plan = Croft3D((16, 16, 16), problem="r2c", strategy="packed")
    x = jnp.asarray(np.random.default_rng(0).standard_normal(
        (16, 16, 16)), jnp.float32)
    jax.block_until_ready(plan.inverse(plan.forward(x)))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        with tracer.profiler_sink(), TraceAnnotation("bench.window"):
            for _ in range(2):
                with TraceAnnotation("bench.call.forward"):
                    y = plan.forward(x)
                with TraceAnnotation("bench.call.inverse"):
                    z = plan.inverse(y)
                with TraceAnnotation("bench.wait"):
                    z.block_until_ready()
    texts = {}

    def hlo_for(modules):
        # the programs the trace ran, each compiled again for its text
        for mod in modules:
            texts[mod] = plan.lower(mod.replace("jit_croft_", "")).compile(
                ).as_text()
        return texts
    ops, spans, croft, runs = ts.load_xplane(tr.find_xplane(str(tmp_path)),
                                             hlo_for)
    assert runs == {}
    assert set(texts) == {"jit_croft_forward", "jit_croft_inverse"}
    calls = [s for s in spans if s.name == "bench.call.forward"]
    fwd = [s for s in croft if s.name == "croft.forward"]
    assert len(fwd) == len(calls) == 2
    for call, span in zip(calls, fwd):
        assert call.start <= span.start <= span.end <= call.end
    assert "croft.forward" not in {s.name for s in spans}
    assert ops and all(o.scope for o in ops)
    assert {o.program for o in ops} == set(texts)
    assert len({o.run for o in ops}) == 4
    red = ts.reduce(ops, spans, croft)
    m = ts.metrics(red, steps=2)
    assert m["unscoped_ms"] == 0 and m["dft_ms"] > 0 and m["relayout_ms"] > 0
    idle = sum(g[1] - g[0] for g in red.base.devices["cpu:0"].gaps)
    assert idle == pytest.approx(red.mean("idle_in_program_ns")
                                 + red.mean("idle_between_ns")
                                 + red.mean("idle_edge_ns"))


def test_chunks_of_a_pipelined_stage_read_apart():
    """A K=2 stage's chunks keep their own keys in ``scopes``: chunk 0's
    transpose beside chunk 1's DFT, each with its own time.  XLA's own
    moves are time without a role, never relayout time."""
    stage = "jit(croft_forward)/croft.stage.x-fft+xy/"
    ops = [ScopedOp("d0", "d0", 0, 10, "fft", stage + "k0/croft.dft/dot",
                    "m", 1),
           ScopedOp("d0", "t0", 10, 40, "collective",
                    stage + "k0/croft.transpose/all_to_all", "m", 1),
           ScopedOp("d0", "d1", 12, 22, "fft", stage + "k1/croft.dft/dot",
                    "m", 1),
           ScopedOp("d0", "t1", 40, 60, "collective",
                    stage + "k1/croft.transpose/all_to_all", "m", 1),
           ScopedOp("d0", "c", 60, 64, "fft", stage + "croft.relayout/cat",
                    "m", 1),
           ScopedOp("d0", "x", 64, 70, "fft", "croft.stage.x-fft+xy/(xla)",
                    "m", 1)]
    red = ts.reduce(ops, [Span("bench.window", 0, 100)])
    scopes = dict(ts.breakdown(red, steps=1)["scopes"])
    key = "croft.stage.x-fft+xy/"
    assert scopes[key + "k0/croft.transpose"] == pytest.approx(30e-6)
    assert scopes[key + "k1/croft.transpose"] == pytest.approx(20e-6)
    assert scopes[key + "k0/croft.dft"] == pytest.approx(10e-6)
    assert scopes[key + "k1/croft.dft"] == pytest.approx(10e-6)
    assert scopes[key + "croft.relayout"] == pytest.approx(4e-6)
    assert scopes[key + "(xla)"] == pytest.approx(6e-6)
    m = ts.metrics(red, steps=1)
    assert m["relayout_ms"] == pytest.approx(4e-6)
    assert m["xla_moves_ms"] == m["unscoped_ms"] == pytest.approx(6e-6)


@pytest.mark.parametrize("label,opcodes,ok", [
    ("dot.1 dot", True, True),
    ("v.0 custom-call:X64SplitLow", True, True),
    ("dot.1 fusion", True, False),         # another opcode
    ("fusion.9 fusion", True, False),      # not in the text
    ("dot.1", False, True),                # a CPU event: the name alone
    ("fusion.9", False, False),
])
def test_attach_checks_each_op_against_its_program(label, opcodes, ok):
    ops = [Op("d0", label, 0, 1, "fft"), Op("d0", "add.7 add", 1, 2, "fft")]

    def where(op):   # the second op's program has no text: not checked
        return ("jit_croft_forward", 3) if op.start == 0 else ("other", 4)
    texts = {"jit_croft_forward": HLO}
    if not ok:
        with pytest.raises(ts.TraceMismatch):
            ts.attach(ops, where, texts, opcodes=opcodes)
        return
    got = ts.attach(ops, where, texts, opcodes=opcodes)
    assert [(o.program, o.run) for o in got] == [("jit_croft_forward", 3),
                                                 ("other", 4)]
    assert got[0].scope == ts.op_names(HLO)[label.split()[0]]
    assert got[1].scope == ""
