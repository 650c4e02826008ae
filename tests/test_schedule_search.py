"""Schedule-space search: candidate tokens, enumerator pruning, dedup,
per-stage cost dispatch, adjoint/inverse of searched pipelines, wisdom
round trips, and multi-device numerics of schedules no fixed builder
can produce.

Golden ``sched-*`` snapshots pin the searched stage structure (including
the ``impl=``/``K=`` per-stage override rendering) the same way
``test_schedule.py`` pins the fixed builders' output.
"""

import dataclasses
import json

import jax.numpy as jnp
import pytest

from conftest import run_multidevice
from repro.core import Decomposition, FFTOptions
from repro.core import schedule as schedule_lib
from repro.core.distributed import build_schedule
from repro.grad import adjoint_schedule
from repro.tuning import candidates as cand_lib
from repro.tuning import cost_model, planner, wisdom as wisdom_lib
from repro.tuning.candidates import ScheduleCandidate, StageSpec

SIZES = {"data": 2, "model": 4}
PENCIL = Decomposition("pencil", ("data", "model"))

# the gate shape: z so short that stage 0's chunk axis cannot split,
# which is what makes mixed per-stage impls win (see
# test_search_beats_every_fixed_builder_at_gate_point)
GATE_SHAPE = (512, 512, 4)

MIXED_KEY = ("sched:pencil[data,model]|k1/matmul/spectral/alltoall/"
             "pipelined|f0.t0s0c1h2r;f1.t1s1c2h0k2;f2")
FUSED_KEY = ("sched:pencil[data,model]|k1/matmul/natural/alltoall/"
             "pipelined|f0.t0s0c1h2;f1.t1s1c2h0;f2.t1s2c1h0;t0s1c0h2")
SPLIT_KEY = ("sched:slab[data+model]|k1/matmul/spectral/alltoall/"
             "pipelined|f0;f1;t0s0c2h1;f2")

GOLDEN = {
    "sched-mixed-impls": (MIXED_KEY, """\
schedule sched/pencil[data,model] sign=-1
  in : C(Nx, Ny/data, Nz/model)
  0 x-fft+xy: fft[x]@s0 | a2a[data] split=0 concat=1 chunk=2 impl=ring -> C(Nx/data, Ny, Nz/model)
  1 y-fft+yz: fft[y]@s1 | a2a[model] split=1 concat=2 chunk=0 K=2 -> C(Nx/data, Ny/model, Nz)
  2 z-fft: fft[z]@s2 -> C(Nx/data, Ny/model, Nz)
  out: C(Nx/data, Ny/model, Nz)"""),
    "sched-fused-natural": (FUSED_KEY, """\
schedule sched/pencil[data,model] sign=-1
  in : C(Nx, Ny/data, Nz/model)
  0 x-fft+xy: fft[x]@s0 | a2a[data] split=0 concat=1 chunk=2 -> C(Nx/data, Ny, Nz/model)
  1 y-fft+yz: fft[y]@s1 | a2a[model] split=1 concat=2 chunk=0 -> C(Nx/data, Ny/model, Nz)
  2 z-fft+zy: fft[z]@s2 | a2a[model] split=2 concat=1 chunk=0 -> C(Nx/data, Ny, Nz/model)
  3 move-yx: a2a[data] split=1 concat=0 chunk=2 -> C(Nx, Ny/data, Nz/model)
  out: C(Nx, Ny/data, Nz/model)"""),
    "sched-split-slab": (SPLIT_KEY, """\
schedule sched/slab[data+model] sign=-1
  in : C(Nx, Ny, Nz/data/model)
  0 x-fft: fft[x]@s0 -> C(Nx, Ny, Nz/data/model)
  1 y-fft: fft[y]@s1 -> C(Nx, Ny, Nz/data/model)
  2 move-xz: a2a[data+model] split=0 concat=2 chunk=1 -> C(Nx/data/model, Ny, Nz)
  3 z-fft: fft[z]@s2 -> C(Nx/data/model, Ny, Nz)
  out: C(Nx/data/model, Ny, Nz)"""),
}


# --- golden snapshots --------------------------------------------------------

@pytest.mark.parametrize("key", sorted(GOLDEN))
def test_golden_searched_schedules(key):
    token, want = GOLDEN[key]
    cand = ScheduleCandidate.from_plan_key(token)
    assert cand.build_schedule().describe() == want, (
        f"searched stage structure of {key} changed — if intentional, "
        "update the golden AND re-verify numerics + cost rankings")


# --- plan tokens -------------------------------------------------------------

def test_token_round_trip_exact():
    for token, _ in GOLDEN.values():
        cand = ScheduleCandidate.from_plan_key(token)
        assert cand.plan_key == token
        again = ScheduleCandidate.from_plan_key(cand.plan_key)
        assert again == cand
        assert (again.build_schedule().describe()
                == cand.build_schedule().describe())


def test_token_round_trip_enumerated():
    cands = cand_lib.enumerate_schedule_candidates((64, 64, 4), SIZES)
    assert cands, "enumerator returned nothing"
    for cand in cands[:200]:
        assert (ScheduleCandidate.from_plan_key(cand.plan_key).plan_key
                == cand.plan_key)


def test_grad_token_round_trip():
    cand = ScheduleCandidate.from_plan_key(MIXED_KEY)
    grad = dataclasses.replace(cand, problem="c2c_grad")
    assert grad.plan_key.endswith("|c2c_grad:")  # |problem:strategy tail
    back = cand_lib.candidate_from_plan_key(grad.plan_key)
    assert back == grad


def test_bad_tokens_raise_valueerror():
    for bad in ("sched:", "sched:pencil[data,model]",
                "sched:pencil[data,model]|k1/matmul/natural/alltoall"
                "/pipelined|f9", MIXED_KEY + ";t5s0c1h2"):
        with pytest.raises(ValueError):
            ScheduleCandidate.from_plan_key(bad)


# --- enumerator + dedup (satellite: no candidate measured twice) -------------

def test_enumerator_excludes_fixed_expressible():
    cands = cand_lib.enumerate_schedule_candidates((64, 64, 64), SIZES)
    for cand in cands:
        assert cand.as_options_candidate() is None, (
            f"{cand.plan_key} is expressible by a fixed builder and "
            "should have been excluded")


def test_homogeneous_overrides_normalize_to_options_candidate():
    # per-stage (ring, ring) with matching Ks is the same pipeline as
    # the scalar transpose_impl="ring" knob — satellite-1's double-
    # measurement bug in spec form
    fixed = cand_lib.Candidate(
        PENCIL, FFTOptions(overlap_k=1, transpose_impl="ring",
                           output_layout="spectral"))
    wrapped = ScheduleCandidate.from_candidate(fixed)
    redundant = dataclasses.replace(
        wrapped, stages=tuple(
            dataclasses.replace(sp, impl="ring", k=1)
            if sp.comm is not None else sp for sp in wrapped.stages))
    eq = redundant.as_options_candidate()
    assert eq is not None and eq.plan_key == fixed.plan_key
    deduped = cand_lib.dedupe_candidates([fixed, redundant, wrapped])
    assert [c.plan_key for c in deduped] == [fixed.plan_key]


def test_dedupe_no_duplicate_plan_keys():
    fixed = cand_lib.enumerate_candidates(GATE_SHAPE, SIZES)
    searched = cand_lib.enumerate_schedule_candidates(GATE_SHAPE, SIZES)
    deduped = cand_lib.dedupe_candidates(list(fixed) + list(searched))
    keys = [c.plan_key for c in deduped]
    assert len(keys) == len(set(keys))
    # dedup must not drop the distinct pipelines
    assert len(deduped) >= len(fixed)


def test_enumerator_prunes_invalid_chunking():
    # z=4 over model=4 leaves one z plane per device: any candidate
    # whose layouts demand a finer split must have been pruned
    for cand in cand_lib.enumerate_schedule_candidates((8, 8, 4), SIZES):
        cand.validate((8, 8, 4), SIZES)


def test_ring_on_folded_communicator_rejected():
    slab = ScheduleCandidate.from_plan_key(SPLIT_KEY)
    ringy = dataclasses.replace(
        slab, stages=tuple(
            dataclasses.replace(sp, impl="ring") if sp.comm is not None
            else sp for sp in slab.stages))
    with pytest.raises(ValueError):
        ringy.validate((64, 64, 8), SIZES)


# --- per-stage knob threading ------------------------------------------------

def test_stage_override_resolution():
    opts = FFTOptions(overlap_k=4, transpose_impl="alltoall")
    sched = ScheduleCandidate.from_plan_key(MIXED_KEY).build_schedule()
    st_ring, st_a2a = sched.stages[0], sched.stages[1]
    assert schedule_lib.stage_transpose_impl(st_ring, opts) == "ring"
    assert schedule_lib.stage_transpose_impl(st_a2a, opts) == "alltoall"
    assert schedule_lib.stage_overlap_k(st_a2a, opts) == 2
    # None-override stages inherit the plan options
    assert schedule_lib.stage_overlap_k(st_ring, opts) == 4


def test_effective_k_respects_stage_overrides():
    sched = ScheduleCandidate.from_plan_key(MIXED_KEY).build_schedule()
    # base K=1, stage 1 overrides K=2 (x extent 512/2 divides)
    assert tuple(sched.effective_k(GATE_SHAPE, SIZES, 1)) == (1, 2)
    # the override also caps: indivisible extents still collapse to 1
    assert sched.effective_k((512, 512, 2), {"data": 2, "model": 1},
                             1)[1] == 2


# --- adjoint of searched schedules -------------------------------------------

def test_adjoint_preserves_overrides_and_layouts():
    for token, _ in GOLDEN.values():
        sched = ScheduleCandidate.from_plan_key(token).build_schedule()
        adj = adjoint_schedule(sched)
        # the adjoint must consume the forward's output layout and emit
        # its input layout — any searched transpose order included
        assert str(adj.layout_in) == str(sched.layout_out)
        assert str(adj.layout_out) == str(sched.layout_in)
        fwd_overrides = sorted(
            (str(st.transpose_impl), st.overlap_k or 0)
            for st in sched.stages if st.comm_axis is not None)
        adj_overrides = sorted(
            (str(st.transpose_impl), st.overlap_k or 0)
            for st in adj.stages if st.comm_axis is not None)
        assert fwd_overrides == adj_overrides


def test_predicted_collectives_forward_and_adjoint():
    cand = ScheduleCandidate.from_plan_key(MIXED_KEY)
    sched = cand.build_schedule()
    shape = (32, 32, 4)
    pred = cost_model.predicted_collectives(sched, shape, SIZES, cand.opts)
    # stage 0: ring over data (P=2), K_eff 1 -> 1 permute round;
    # stage 1: alltoall K=2 -> 2 all-to-alls
    assert pred == {"all-to-all": 2, "collective-permute": 1}
    adj = adjoint_schedule(sched)
    assert (cost_model.predicted_collectives(adj, shape, SIZES, cand.opts)
            == pred)


# --- per-stage cost model ----------------------------------------------------

def test_searched_cost_rows_carry_impls():
    cand = ScheduleCandidate.from_plan_key(MIXED_KEY)
    rows = cost_model.per_stage_costs(GATE_SHAPE, cand, SIZES)
    impls = [r["impl"] for r in rows if r.get("collective_s")]
    assert impls == ["ring", "alltoall"]
    cost = cost_model.analytic_cost(GATE_SHAPE, cand, SIZES)
    assert cost.total_s > 0


def test_mixed_beats_homogeneous_at_gate_point():
    """The deterministic win regime the search exists for: stage 0's
    chunk axis (z, one plane per model rank) cannot split, so a
    homogeneous K leaves stage 0's all-to-all unhidden while a
    homogeneous ring pays P-1 latencies on the big communicator.  The
    mixed plan takes ring where chunking is impossible and pipelined
    alltoall where it is not."""
    mixed = ScheduleCandidate.from_plan_key(MIXED_KEY)
    base = mixed.opts
    hom_ring = dataclasses.replace(
        mixed, opts=dataclasses.replace(base, transpose_impl="ring"),
        stages=tuple(dataclasses.replace(sp, impl=None, k=None)
                     for sp in mixed.stages))
    hom_a2a_k2 = dataclasses.replace(
        mixed, opts=dataclasses.replace(base, overlap_k=2),
        stages=tuple(dataclasses.replace(sp, impl=None, k=None)
                     for sp in mixed.stages))
    t = {c: cost_model.analytic_cost(GATE_SHAPE, c, SIZES).total_s
         for c in (mixed, hom_ring, hom_a2a_k2)}
    assert t[mixed] < t[hom_ring]
    assert t[mixed] < t[hom_a2a_k2]


@pytest.fixture(scope="module")
def gate_rankings():
    """Best fixed-builder plan and best searched pipeline at the gate
    point, both priced by the same per-stage combine (fixed candidates
    wrapped as schedule candidates)."""
    fixed = [ScheduleCandidate.from_candidate(c)
             for c in cand_lib.enumerate_candidates(GATE_SHAPE, SIZES)]
    searched = cand_lib.enumerate_schedule_candidates(GATE_SHAPE, SIZES)
    return (cost_model.rank_candidates(GATE_SHAPE, fixed, SIZES)[0],
            cost_model.rank_candidates(GATE_SHAPE, searched, SIZES)[0])


def test_search_beats_every_fixed_builder_at_gate_point(gate_rankings):
    (_, c_fixed), (_, c_searched) = gate_rankings
    assert c_searched.total_s < c_fixed.total_s


def test_search_winner_is_fixed_inexpressible(gate_rankings):
    """The winning pipeline is a point no fixed builder can emit."""
    _, (best, _) = gate_rankings
    assert best.as_options_candidate() is None, best.plan_key


def test_search_winner_compiles_to_predicted_collectives(gate_rankings):
    """The gate winner's structure compiled at (32, 32, 4) on the 2x4
    mesh holds exactly the per-stage predicted collectives: ring stages
    K_eff*(P-1) collective-permutes, alltoall stages K_eff all-to-alls.
    A per-stage override the executor ignored would compile to the
    homogeneous counts."""
    _, (best, _) = gate_rankings
    run_multidevice(f"""
from repro.core import Croft3D
from repro.launch import hlo_cost
from repro.launch.mesh import make_mesh
from repro.tuning import cost_model
from repro.tuning.candidates import ScheduleCandidate
shape = (32, 32, 4)
axes = {SIZES!r}
cand = ScheduleCandidate.from_plan_key({best.plan_key!r})
cand.validate(shape, axes)
pred = cost_model.predicted_collectives(cand.build_schedule(), shape, axes,
                                        cand.opts)
plan = Croft3D(shape, mesh=make_mesh(tuple(axes.values()), tuple(axes)),
               schedule=cand)
got = hlo_cost.analyze(plan.lower_forward().compile().as_text()).collectives
got = {{k: int(v["count"]) for k, v in got.items() if v["count"]}}
pred = {{k: v for k, v in pred.items() if v}}
assert got == pred and "collective-permute" in got, (got, pred)
print("OK", got)
""")


def test_fixed_candidate_costs_unchanged():
    """The legacy options-space cost formula is pinned bit-identical:
    adding the per-stage combine for searched candidates must not move
    any fixed candidate's score (wisdom files rank with these)."""
    fixed = cand_lib.Candidate(
        PENCIL, FFTOptions(overlap_k=2, output_layout="spectral"))
    cost = cost_model.analytic_cost((64, 64, 64), fixed, SIZES)
    again = cost_model.analytic_cost((64, 64, 64), fixed, SIZES)
    assert cost.total_s == again.total_s
    assert not getattr(fixed, "is_schedule", False)


# --- planner + wisdom --------------------------------------------------------

def test_tune_schedule_search_model_mode(tmp_path):
    wpath = str(tmp_path / "w.json")
    r = planner.tune(GATE_SHAPE, axis_sizes=SIZES, mode="model",
                     search="schedule", wisdom_path=wpath)
    assert r.source == "model"
    labels = {row["label"] for row in r.ranked}
    assert any(lb.startswith("sched:") for lb in labels), (
        "schedule search produced no searched candidates in the ranking")
    # wisdom round trip: the stored entry reconstructs the same plan
    r2 = planner.tune(GATE_SHAPE, axis_sizes=SIZES, mode="wisdom",
                      search="schedule", wisdom_path=wpath)
    assert r2.source == "wisdom"
    if r.schedule is not None:
        assert r2.schedule is not None
        assert r2.schedule.plan_key == r.schedule.plan_key


def test_tune_schedule_search_rejects_r2c():
    with pytest.raises(ValueError):
        planner.tune((32, 32, 32), axis_sizes=SIZES, mode="model",
                     search="schedule", problem="r2c")


def test_wisdom_entry_schedule_round_trip(tmp_path):
    cand = ScheduleCandidate.from_plan_key(MIXED_KEY)
    entry = wisdom_lib.WisdomEntry.from_candidate(cand, "model",
                                                  model_s=1e-4)
    assert entry.schedule == MIXED_KEY
    back = wisdom_lib.WisdomEntry.from_json(entry.to_json()).candidate()
    assert back == cand
    # persists through the file format
    wpath = str(tmp_path / "w.json")
    wisdom_lib.merge_entries(wpath, {"k": entry})
    loaded = wisdom_lib.Wisdom.load(wpath).entries["k"]
    assert loaded.candidate().plan_key == MIXED_KEY


def test_legacy_wisdom_entries_still_load(tmp_path):
    """Wisdom written before the schedule field existed must keep
    loading, merging and planning — the on-disk compat contract."""
    legacy = {"version": 1, "entries": {"legacy-key": {
        "decomp_kind": "pencil", "decomp_axes": ["data", "model"],
        "opts": {"overlap_k": 2, "transpose_impl": "alltoall",
                 "output_layout": "spectral"},
        "source": "measure", "measured_s": 5e-5}}}
    p = tmp_path / "legacy.json"
    p.write_text(json.dumps(legacy))
    w = wisdom_lib.Wisdom.load(str(p))
    cand = w.entries["legacy-key"].candidate()
    assert not getattr(cand, "is_schedule", False)
    assert cand.decomp.kind == "pencil"
    assert cand.opts.overlap_k == 2
    assert build_schedule(cand.decomp, cand.opts).describe()
    # merging a schedule entry alongside leaves the legacy entry intact
    sched_entry = wisdom_lib.WisdomEntry.from_candidate(
        ScheduleCandidate.from_plan_key(MIXED_KEY), "model", model_s=1e-4)
    wisdom_lib.merge_entries(str(p), {"sched-key": sched_entry})
    w2 = wisdom_lib.Wisdom.load(str(p))
    assert w2.entries["legacy-key"].measured_s == 5e-5
    assert w2.entries["sched-key"].candidate().plan_key == MIXED_KEY


def test_wisdom_cli_renders_schedule_entries(tmp_path, capsys):
    wpath = str(tmp_path / "w.json")
    entry = wisdom_lib.WisdomEntry.from_candidate(
        ScheduleCandidate.from_plan_key(MIXED_KEY), "model", model_s=1e-4)
    wisdom_lib.merge_entries(wpath, {"some-key": entry})
    assert wisdom_lib._main(["show", wpath]) == 0
    out = capsys.readouterr().out
    assert "<unreadable entry>" not in out
    assert "stages: x-fft+xy[ring,K=1] -> y-fft+yz[alltoall,K=2] " \
           "-> z-fft" in out
    assert wisdom_lib._main(["stats", wpath]) == 0
    out = capsys.readouterr().out
    assert "/sched" in out and "searched:   1 schedule-keyed entry" in out


# --- multi-device numerics ---------------------------------------------------

def test_searched_schedules_execute_and_invert():
    """Forward == np.fft.fftn and inverse round-trips for pipelines the
    fixed builders cannot produce (fused natural, split slab, mixed
    impls), plus bitwise parity with the fixed builder where the spaces
    overlap."""
    run_multidevice(f"""
import dataclasses, numpy as np, jax, jax.numpy as jnp
from repro.core import Croft3D, Decomposition, FFTOptions
from repro.tuning.candidates import Candidate, ScheduleCandidate
from repro.launch.mesh import make_mesh

mesh = make_mesh((2, 4), ("data", "model"))
shape = (16, 16, 8)
rng = np.random.default_rng(0)
x = (rng.standard_normal(shape)
     + 1j * rng.standard_normal(shape)).astype(np.complex64)
ref = np.fft.fftn(x).astype(np.complex64)

for token in [{MIXED_KEY!r}, {FUSED_KEY!r}, {SPLIT_KEY!r}]:
    cand = ScheduleCandidate.from_plan_key(token)
    plan = Croft3D(shape, mesh=mesh, schedule=cand)
    xd = jax.device_put(jnp.asarray(x), plan.input_sharding)
    y = plan.forward(xd)
    got = np.asarray(jax.device_get(y))
    err = np.max(np.abs(got - ref)) / np.max(np.abs(ref))
    assert err < 1e-4, (token, err)
    xb = np.asarray(jax.device_get(plan.inverse(y)))
    rerr = np.max(np.abs(xb - x)) / np.max(np.abs(x))
    assert rerr < 1e-4, (token, rerr)

# bitwise parity: a fixed plan wrapped as a (no-override) schedule
# candidate must compile to the numerically identical program
fixed = Candidate(Decomposition("pencil", ("data", "model")),
                  FFTOptions(overlap_k=2, output_layout="spectral"))
wrapped = ScheduleCandidate.from_candidate(fixed)
pf = Croft3D(shape, mesh, fixed.decomp, fixed.opts)
ps = Croft3D(shape, mesh=mesh, schedule=wrapped)
xd = jax.device_put(jnp.asarray(x), pf.input_sharding)
assert bool(jnp.array_equal(pf.forward(xd), ps.forward(xd))), \\
    "wrapped fixed pipeline diverged bitwise from the fixed builder"
print("OK")
""")


def test_searched_schedule_differentiates():
    """grad through a searched mixed-impl plan matches the spectral
    Parseval identity; the custom VJP replays the adjoint schedule, so
    this exercises adjoint layout validation end to end."""
    run_multidevice(f"""
import numpy as np, jax, jax.numpy as jnp
from repro.core import Croft3D
from repro.tuning.candidates import ScheduleCandidate
from repro.launch.mesh import make_mesh

mesh = make_mesh((2, 4), ("data", "model"))
shape = (16, 16, 8)
plan = Croft3D(shape, mesh=mesh,
               schedule=ScheduleCandidate.from_plan_key({MIXED_KEY!r}))
rng = np.random.default_rng(1)
x = jnp.asarray((rng.standard_normal(shape)
                 + 1j * rng.standard_normal(shape)).astype(np.complex64))
x = jax.device_put(x, plan.input_sharding)

def loss(v):
    y = plan.forward(v)
    return jnp.sum(jnp.real(y * jnp.conj(y)))

g = jax.grad(loss)(x)
# JAX's complex-grad convention: grad sum|Fx|^2 = 2 conj(F^H F x)
# = 2 N conj(x) for the unnormalized DFT (Parseval)
n = float(np.prod(shape))
np.testing.assert_allclose(np.asarray(jax.device_get(g)),
                           2 * n * np.conj(np.asarray(jax.device_get(x))),
                           rtol=1e-3, atol=1e-3)
print("OK")
""")


def test_ring_rounds_carry_stage_and_transpose_scopes():
    """Every ppermute round of a ring stage is named by its stage and the
    transpose role in the compiled program (1 round over the P=2 axis,
    3 over the P=4 one), and the ring matches the alltoall numerically."""
    run_multidevice("""
import re, collections
import numpy as np, jax, jax.numpy as jnp
from repro.core import Croft3D, Decomposition, FFTOptions
from repro.tuning.measure import _random_input
from repro.launch.mesh import make_mesh

mesh = make_mesh((2, 4), ("data", "model"))
dec = Decomposition("pencil", ("data", "model"))
shape = (16, 16, 8)
ring = Croft3D(shape, mesh, dec, FFTOptions(
    overlap_k=1, transpose_impl="ring", output_layout="spectral"))
a2a = Croft3D(shape, mesh, dec, FFTOptions(
    overlap_k=1, output_layout="spectral"))
rounds = collections.Counter()
for line in ring.lower_forward().compile().as_text().splitlines():
    if re.search(r" collective-permute(-start)?\\(", line):
        name = re.search(r'op_name="([^"]*)"', line).group(1)
        stage = re.search(r"croft\\.stage\\.([^/]+)/croft\\.transpose/", name)
        rounds[stage.group(1)] += 1
assert rounds == {"x-fft+xy": 1, "y-fft+yz": 3}, rounds
x = _random_input(shape, jnp.complex64, ring.input_sharding)
np.testing.assert_allclose(np.asarray(jax.device_get(ring.forward(x))),
                           np.asarray(jax.device_get(a2a.forward(x))),
                           rtol=1e-5, atol=1e-4)
print("OK")
""")


def test_tune_measure_schedule_search_end_to_end():
    """measure-mode schedule search on a live mesh: the winner builds,
    times, persists to wisdom, and a fresh tune reconstructs it."""
    run_multidevice("""
import os, tempfile
import jax, jax.numpy as jnp
from repro.core import Croft3D
from repro.tuning.planner import tune
from repro.launch.mesh import make_mesh

mesh = make_mesh((2, 4), ("data", "model"))
shape = (16, 16, 8)
wpath = os.path.join(tempfile.mkdtemp(), "w.json")
r = tune(shape, mesh, mode="measure", search="schedule", top_k=2,
         wisdom_path=wpath, measure_iters=2, measure_warmup=1)
assert r.measured_s is not None and r.measured_s > 0
plan = Croft3D.tuned(shape, mesh, mode="wisdom", wisdom_path=wpath)
assert plan.tune_result.source == "wisdom"
if r.schedule is not None:
    assert plan.schedule is not None
    assert plan.schedule.plan_key == r.schedule.plan_key
x = jnp.ones(shape, jnp.complex64)
x = jax.device_put(x, plan.input_sharding)
jax.block_until_ready(plan.forward(x))
print("OK")
""")
