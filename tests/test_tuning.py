"""Autotuning planner (repro.tuning): candidate generation, analytic cost
model, wisdom persistence, and end-to-end tuned plans on 8 virtual devices.

Everything except the final tuned-plan test runs meshless in this process
(the planner's mode="model"/"wisdom" paths are zero-execution by design).
"""

import dataclasses
import json
import os

import jax.numpy as jnp
import pytest

from conftest import run_multidevice
from repro.core import Decomposition, FFTOptions
from repro import tuning

SIZES = {"data": 2, "model": 4}
SHAPE = (32, 32, 32)


# --- candidate generation ---------------------------------------------------

def test_candidates_respect_divisibility():
    cands = tuning.enumerate_candidates(SHAPE, SIZES)
    assert cands, "search space must be non-empty for a divisible shape"
    for c in cands:
        # every emitted candidate revalidates cleanly
        c.decomp.validate(SHAPE, SIZES, c.opts.overlap_k)
    kinds = {c.decomp.kind for c in cands}
    assert kinds == {"slab", "pencil"}  # 2-axis mesh: no 3-slot cell


def test_candidates_reject_indivisible_shapes():
    # Ny=24 not divisible by the 4-sized axis in any pencil orientation
    # that also needs Nx % 4; slab over the folded 8 needs Nz % 8
    cands = tuning.enumerate_candidates((30, 30, 30), SIZES)
    assert cands == []
    # mixed: only configurations dividing 16 on the z axis survive
    ok = tuning.enumerate_candidates((32, 32, 16), SIZES)
    for c in ok:
        c.decomp.validate((32, 32, 16), SIZES, c.opts.overlap_k)


def test_candidates_cover_option_matrix():
    cands = tuning.enumerate_candidates(SHAPE, SIZES)
    ks = {c.opts.overlap_k for c in cands}
    impls = {c.opts.local_impl for c in cands}
    layouts = {c.opts.output_layout for c in cands}
    assert ks == {1, 2, 4}
    assert impls == {"matmul", "stockham", "xla"}
    assert layouts == {"natural", "spectral"}
    # production search space excludes the paper-baseline knobs (no-plan
    # caching, the pairwise FFTW3 emulation) but DOES carry the ring
    # transpose wherever it can trace — it is a real overlap strategy,
    # ranked by the cost model's alpha/beta split, not a baseline
    assert all(c.opts.plan_cache for c in cands)
    timpls = {c.opts.transpose_impl for c in cands}
    assert timpls == {"alltoall", "ring"}
    with_bases = tuning.enumerate_candidates(SHAPE, SIZES,
                                             include_baselines=True)
    assert any(not c.opts.plan_cache for c in with_bases)
    assert any(c.opts.transpose_impl == "pairwise" for c in with_bases)


def test_default_candidate_matches_mesh_rank():
    assert tuning.default_candidate(SHAPE, {"p": 8}).decomp.kind == "slab"
    assert tuning.default_candidate(SHAPE, SIZES).decomp.kind == "pencil"
    c3 = tuning.default_candidate(SHAPE, {"a": 2, "b": 2, "c": 2})
    assert c3.decomp.kind == "cell"


# --- analytic cost model ----------------------------------------------------

def test_cost_model_ranks_spectral_below_natural_on_comm_bytes():
    dec = Decomposition("pencil", ("data", "model"))
    nat = tuning.analytic_cost(
        SHAPE, tuning.Candidate(dec, FFTOptions(output_layout="natural")),
        SIZES)
    spec = tuning.analytic_cost(
        SHAPE, tuning.Candidate(dec, FFTOptions(output_layout="spectral")),
        SIZES)
    assert spec.collective_bytes == nat.collective_bytes / 2
    assert spec.total_s < nat.total_s


def test_cost_model_penalizes_pairwise_and_replan():
    dec = Decomposition("slab", ("model",))
    base = tuning.analytic_cost(
        SHAPE, tuning.Candidate(dec, FFTOptions(overlap_k=1)), SIZES)
    pair = tuning.analytic_cost(
        SHAPE, tuning.Candidate(
            dec, FFTOptions(overlap_k=1, transpose_impl="pairwise")), SIZES)
    noplan = tuning.analytic_cost(
        SHAPE, tuning.Candidate(
            dec, FFTOptions(overlap_k=1, plan_cache=False)), SIZES)
    assert pair.n_collectives > base.n_collectives
    assert pair.total_s > base.total_s
    assert noplan.replan_s > 0 and noplan.total_s > base.total_s


def test_cost_model_overlap_hides_communication():
    """At a comm-bound size, K>=2 must beat K=1 with the same knobs —
    the paper's central claim, reproduced by the model."""
    dec = Decomposition("pencil", ("data", "model"))
    big = (256, 256, 256)
    k1 = tuning.analytic_cost(
        big, tuning.Candidate(dec, FFTOptions(overlap_k=1)), SIZES)
    k2 = tuning.analytic_cost(
        big, tuning.Candidate(dec, FFTOptions(overlap_k=2)), SIZES)
    assert k2.total_s < k1.total_s


def test_rank_candidates_is_deterministic_and_sorted():
    cands = tuning.enumerate_candidates(SHAPE, SIZES)
    r1 = tuning.rank_candidates(SHAPE, cands, SIZES)
    r2 = tuning.rank_candidates(SHAPE, cands, SIZES)
    assert [c.label for c, _ in r1] == [c.label for c, _ in r2]
    totals = [b.total_s for _, b in r1]
    assert totals == sorted(totals)


# --- wisdom persistence -----------------------------------------------------

def test_wisdom_save_load_roundtrip(tmp_path):
    path = str(tmp_path / "wisdom.json")
    cand = tuning.Candidate(Decomposition("pencil", (("a", "b"), "c")),
                            FFTOptions(overlap_k=4, output_layout="spectral"))
    key = tuning.wisdom_key(SHAPE, {"a": 2, "b": 2, "c": 2},
                            jnp.complex64, "cpu")
    w = tuning.Wisdom(path=path)
    w.record(key, tuning.WisdomEntry.from_candidate(
        cand, "measure", model_s=1e-3, measured_s=5e-4))
    assert w.save() == path

    w2 = tuning.Wisdom.load(path)
    hit = w2.lookup(key)
    assert hit is not None and hit.measured_s == 5e-4
    got = hit.candidate()
    # nested folded axes survive the JSON round trip as tuples
    assert got.decomp == cand.decomp
    assert got.opts == cand.opts
    # file is plain JSON (exportable/mergeable)
    blob = json.load(open(path))
    assert blob["version"] == 1 and key in blob["entries"]


def test_wisdom_merge_prefers_faster_measurement():
    cand = tuning.Candidate(Decomposition("slab", ("p",)), FFTOptions())
    slow = tuning.WisdomEntry.from_candidate(cand, "measure", measured_s=2e-3)
    fast = tuning.WisdomEntry.from_candidate(
        dataclasses.replace(cand, opts=FFTOptions(overlap_k=4)),
        "measure", measured_s=1e-3)
    a, b = tuning.Wisdom(), tuning.Wisdom()
    a.record("k", slow)
    b.record("k", fast)
    a.merge(b)
    assert a.lookup("k").measured_s == 1e-3
    # modeled entries never displace measured ones
    modeled = tuning.WisdomEntry.from_candidate(cand, "model", model_s=1e-9)
    a.record("k", modeled)
    assert a.lookup("k").measured_s == 1e-3


def test_wisdom_mode_skips_measurement(tmp_path, monkeypatch):
    """mode="wisdom" with a hit must not compile or time anything."""
    path = str(tmp_path / "w.json")
    cand = tuning.Candidate(Decomposition("pencil", ("data", "model")),
                            FFTOptions(output_layout="spectral"))
    key = tuning.wisdom_key(SHAPE, SIZES, jnp.complex64, "any")
    w = tuning.Wisdom(path=path)
    w.record(key, tuning.WisdomEntry.from_candidate(
        cand, "measure", measured_s=1e-3))
    w.save()

    def boom(*a, **k):
        raise AssertionError("measurement ran on a wisdom hit")
    monkeypatch.setattr(tuning.measure, "measure_candidate", boom)
    monkeypatch.setattr(tuning.planner.measure, "measure_candidate", boom)

    r = tuning.tune(SHAPE, axis_sizes=SIZES, mode="wisdom", wisdom_path=path)
    assert r.source == "wisdom"
    assert r.decomp == cand.decomp and r.opts == cand.opts


def test_wisdom_miss_falls_back_to_model_and_records(tmp_path):
    path = str(tmp_path / "w.json")
    r = tuning.tune(SHAPE, axis_sizes=SIZES, mode="wisdom", wisdom_path=path)
    assert r.source == "model"          # miss -> ESTIMATE
    r2 = tuning.tune(SHAPE, axis_sizes=SIZES, mode="wisdom", wisdom_path=path)
    assert r2.source == "wisdom"        # and the estimate was remembered
    assert r2.decomp == r.decomp and r2.opts == r.opts


def test_tune_model_mode_needs_no_devices():
    r = tuning.tune(SHAPE, axis_sizes=SIZES, mode="model")
    assert r.source == "model" and r.model_s > 0
    assert r.decomp.is_valid(SHAPE, SIZES, r.opts.overlap_k)
    with pytest.raises(ValueError):
        tuning.tune(SHAPE, axis_sizes=SIZES, mode="measure")  # needs mesh
    with pytest.raises(ValueError):
        tuning.tune((30, 30, 30), axis_sizes=SIZES, mode="model")


# --- end to end on 8 virtual devices ---------------------------------------

def test_tuned_plan_roundtrip_and_wisdom(tmp_path):
    """Croft3D.tuned matches jnp.fft.fftn, beats-or-ties the default plan,
    and persists reusable wisdom."""
    wp = str(tmp_path / "wisdom.json")
    run_multidevice(f"""
import numpy as np, jax, jax.numpy as jnp
from repro.core import Croft3D, Decomposition, FFTOptions
from repro import tuning
mesh = jax.make_mesh((2,4), ("data","model"),
                     axis_types=(jax.sharding.AxisType.Auto,)*2)
N = 32
plan = Croft3D.tuned((N,N,N), mesh, mode="measure", wisdom_path={wp!r},
                     top_k=3, measure_iters=3)
print("chosen:", plan.tune_result.summary())
rng = np.random.RandomState(3)
x = (rng.randn(N,N,N) + 1j*rng.randn(N,N,N)).astype(np.complex64)
xd = jax.device_put(jnp.asarray(x), plan.input_sharding)
y = plan.forward(xd)
ref = jnp.fft.fftn(jnp.asarray(x))
err = float(jnp.max(jnp.abs(y - ref))) / float(jnp.max(jnp.abs(ref)))
assert err < 1e-5, err
xb = plan.inverse(y)
rerr = float(jnp.max(jnp.abs(xb - x)))
assert rerr < 1e-4, rerr

# measured winner is no slower than the hand-picked default plan
dflt = Croft3D((N,N,N), mesh, Decomposition("pencil", ("data","model")),
               FFTOptions())
t_dflt = tuning.time_forward(dflt, warmup=2, iters=3)
assert plan.tune_result.measured_s <= t_dflt * 1.25, (
    plan.tune_result.measured_s, t_dflt)

# the tune= constructor arg reuses the stored wisdom (no re-measuring)
plan2 = Croft3D((N,N,N), mesh, tune="wisdom", wisdom_path={wp!r})
assert plan2.tune_result.source == "wisdom"
assert plan2.decomp == plan.decomp and plan2.opts == plan.opts
y2 = plan2.forward(jax.device_put(jnp.asarray(x), plan2.input_sharding))
assert float(jnp.max(jnp.abs(y2 - y))) == 0.0
print("OK tuned roundtrip err", err, "rerr", rerr)
""", timeout=900)


# --- canonical plan keys (serve plan cache / wisdom) -------------------------

def test_decomposition_token_roundtrip():
    for dec in (Decomposition("slab", ("model",)),
                Decomposition("pencil", ("data", "model")),
                Decomposition("pencil", (("pod", "data"), "model")),
                Decomposition("cell", ("a", "b", "c"))):
        tok = dec.to_token()
        assert Decomposition.from_token(tok) == dec, tok


def test_fftoptions_token_roundtrip():
    for opts in (FFTOptions(),
                 FFTOptions(overlap_k=4, local_impl="stockham",
                            output_layout="spectral", transpose_impl="ring"),
                 FFTOptions(local_impl=("matmul", "stockham", "xla"),
                            overlap_mode=("pipelined", "unrolled",
                                          "unrolled")),
                 FFTOptions(plan_cache=False, overlap_k=1)):
        tok = opts.to_token()
        assert FFTOptions.from_token(tok) == opts, tok


def test_candidate_plan_key_roundtrip_covers_every_knob():
    """plan_key must round trip exactly — including the per-stage
    3-tuples and the r2c strategy axis — so the serving cache can never
    alias two different executables under one key."""
    cands = tuning.enumerate_candidates(
        SHAPE, SIZES, include_baselines=True, heterogeneous_impls=True)
    cands += tuning.enumerate_candidates(SHAPE, SIZES, problem="r2c")
    assert len({c.plan_key for c in cands}) == len(set(cands))
    for c in cands:
        back = tuning.Candidate.from_plan_key(c.plan_key)
        assert back == c, c.plan_key


def test_grad_candidates_mirror_base_space():
    """``c2c_grad``/``r2c_grad`` reuse the base search space knob-for-knob
    (the adjoint is derived, never searched) with only the problem tag
    changed."""
    base = tuning.enumerate_candidates(SHAPE, SIZES)
    grad = tuning.enumerate_candidates(SHAPE, SIZES, problem="c2c_grad")
    assert [(c.decomp, c.opts) for c in grad] \
        == [(c.decomp, c.opts) for c in base]
    assert all(c.problem == "c2c_grad" for c in grad)
    rbase = tuning.enumerate_candidates(SHAPE, SIZES, problem="r2c")
    rgrad = tuning.enumerate_candidates(SHAPE, SIZES, problem="r2c_grad")
    assert [(c.decomp, c.opts, c.strategy) for c in rgrad] \
        == [(c.decomp, c.opts, c.strategy) for c in rbase]
    assert {c.strategy for c in rgrad} == {"embed", "packed"}
    d = tuning.default_candidate(SHAPE, SIZES, problem="r2c_grad")
    assert d is not None and d.problem == "r2c_grad"


def test_grad_plan_keys_roundtrip_and_reject_unknown_problems():
    """Grad plan keys round trip (including strategy=None, which must not
    serialize as the string "None"), and an unknown problem tag is a loud
    ValueError — a stale or foreign wisdom entry becomes a miss upstream,
    never a misparsed plan."""
    cands = (tuning.enumerate_candidates(SHAPE, SIZES, problem="c2c_grad")
             + tuning.enumerate_candidates(SHAPE, SIZES, problem="r2c_grad"))
    assert len({c.plan_key for c in cands}) == len(set(cands))
    for c in cands:
        assert tuning.Candidate.from_plan_key(c.plan_key) == c, c.plan_key
    good = cands[0].plan_key
    with pytest.raises(ValueError, match="unknown problem"):
        tuning.Candidate.from_plan_key(good.replace("c2c_grad", "c2c_hess"))
    # and a grad entry survives the wisdom JSON round trip as a real
    # candidate (so `wisdom show`/`stats` render it, not <unreadable>)
    entry = tuning.WisdomEntry.from_candidate(cands[-1], "measure",
                                              measured_s=1e-3)
    back = tuning.WisdomEntry.from_json(
        json.loads(json.dumps(entry.to_json()))).candidate()
    assert back == cands[-1]


def test_tune_model_mode_grad_problem(tmp_path):
    """mode="model" prices fwd+adjoint for ``_grad`` problems, records
    under the ``|grad`` key, and the entry replays as a wisdom hit."""
    path = str(tmp_path / "w.json")
    r = tuning.tune(SHAPE, axis_sizes=SIZES, mode="model",
                    problem="c2c_grad", wisdom_path=path)
    assert r.key.endswith("|grad")
    base = tuning.tune(SHAPE, axis_sizes=SIZES, mode="model")
    assert r.key != base.key
    hit = tuning.Wisdom.load(path).lookup(r.key)
    assert hit is not None and hit.candidate().problem == "c2c_grad"
    r2 = tuning.tune(SHAPE, axis_sizes=SIZES, mode="wisdom",
                     problem="c2c_grad", wisdom_path=path)
    assert r2.source == "wisdom"
    assert r2.decomp == r.decomp and r2.opts == r.opts


def test_wisdom_cli_tolerates_grad_and_foreign_entries(tmp_path, capsys):
    """``wisdom show``/``stats`` must render ``|grad`` entries and
    survive an entry whose problem tag this version does not know (a
    newer or foreign wisdom file): unreadable at worst, never a crash."""
    from repro.tuning import wisdom as wisdom_lib
    path = str(tmp_path / "w.json")
    tuning.tune(SHAPE, axis_sizes=SIZES, mode="model", problem="r2c_grad",
                wisdom_path=path)
    with open(path) as f:
        blob = json.load(f)
    key, d = next(iter(blob["entries"].items()))
    assert key.endswith("|grad")
    blob["entries"][key.replace("|grad", "|hess")] = dict(d,
                                                          problem="c2c_hess")
    # a foreign writer would not maintain this version's integrity
    # checksum — drop it rather than ship a stale one (a *mismatching*
    # checksum means corruption and is quarantined; see test_resil.py)
    blob.pop("checksum", None)
    with open(path, "w") as f:
        json.dump(blob, f)
    assert wisdom_lib._main(["show", path]) == 0
    assert wisdom_lib._main(["stats", path]) == 0
    out = capsys.readouterr().out
    assert "|grad" in out


def test_candidate_label_distinguishes_overlap_mode():
    """Regression: the planner's measured={label: t} dict used to alias
    candidates differing only in overlap_mode."""
    a = tuning.Candidate(Decomposition("pencil", ("data", "model")),
                         FFTOptions(overlap_mode="pipelined"))
    b = tuning.Candidate(Decomposition("pencil", ("data", "model")),
                         FFTOptions(overlap_mode="unrolled"))
    assert a.label != b.label
