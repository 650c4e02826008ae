"""Real-transform subsystem (repro.real): packed two-for-one r2c/c2r vs
numpy, the embed fallback, the Pallas Hermitian kernels, the guarded
half-slice, per-stage local_impl, and the r2c problem class in the tuner.

Single-device checks run in-process; multi-device and float64 checks run
on 8 virtual CPU devices in subprocesses (see conftest.run_multidevice).
"""

import dataclasses
import json
import math
import os
import subprocess
import sys

import numpy as np
import jax.numpy as jnp
import pytest

from conftest import REPO, SRC, run_multidevice
from repro.core import Decomposition, FFTOptions
from repro.core.rfft import rfft3d, irfft3d
from repro import real as real_lib
from repro.real import packing
from repro import tuning

SIZES = {"data": 2, "model": 4}


# --- local packed path vs numpy ---------------------------------------------

@pytest.mark.parametrize("shape,impl", [
    ((8, 4, 16), "matmul"),      # even everything, pow2
    ((4, 8, 32), "matmul"),      # pairs along y
    ((8, 4, 15), "xla"),         # odd Nz: fold-free two-for-one
    ((9, 6, 15), "xla"),         # odd Nx/Nz
    ((8, 9, 12), "xla"),         # odd Ny: pairs along x instead
])
def test_local_packed_matches_rfftn(shape, impl, rng):
    x = rng.randn(*shape).astype(np.float32)
    opts = FFTOptions(local_impl=impl)
    y = np.asarray(rfft3d(jnp.asarray(x), opts=opts, strategy="packed"))
    ref = np.fft.rfftn(x)
    assert y.shape == ref.shape
    np.testing.assert_allclose(y, ref, atol=3e-5 * np.abs(ref).max())
    xb = np.asarray(irfft3d(jnp.asarray(y), shape[-1], opts=opts,
                            strategy="packed"))
    np.testing.assert_allclose(xb, x, atol=2e-5)


def test_local_packed_equals_embed(rng):
    x = rng.randn(16, 8, 32).astype(np.float32)
    yp = np.asarray(rfft3d(jnp.asarray(x), strategy="packed"))
    ye = np.asarray(rfft3d(jnp.asarray(x), strategy="embed"))
    np.testing.assert_allclose(yp, ye, atol=2e-5 * np.abs(ye).max())


def test_strategy_resolution(rng):
    # all-odd (Nx, Ny): no pairing axis -> explicit packed raises, auto
    # falls back to the (always valid) embedding and still matches numpy
    x = rng.randn(9, 9, 15).astype(np.float32)
    opts = FFTOptions(local_impl="xla")
    with pytest.raises(ValueError, match="packed"):
        rfft3d(jnp.asarray(x), opts=opts, strategy="packed")
    y = np.asarray(rfft3d(jnp.asarray(x), opts=opts))  # auto
    np.testing.assert_allclose(y, np.fft.rfftn(x),
                               atol=3e-5 * np.abs(np.fft.rfftn(x)).max())
    with pytest.raises(ValueError, match="strategy"):
        rfft3d(jnp.asarray(x), opts=opts, strategy="bogus")


def test_rfft3d_rejects_complex(rng):
    with pytest.raises(ValueError, match="real"):
        rfft3d(jnp.ones((4, 4, 4), jnp.complex64))


@pytest.mark.parametrize("nz", [8, 15])
def test_c2r_non_hermitian_input_matches_irfftn(nz, rng):
    """irfftn implicitly projects the DC/Nyquist planes of a non-Hermitian
    half spectrum; the packed path must apply the same projection (e.g.
    derivative filters 1j*kx leave a surviving anti-Hermitian Nyquist
    plane — the Burgers driver's exact usage)."""
    n = 8
    x = rng.randn(n, n, nz)
    kx = np.fft.fftfreq(n, d=1.0 / n)[:, None, None]
    y = (1j * kx * np.fft.rfftn(x) * (1 + 0.3j)).astype(np.complex64)
    axes = [0, 1, 2]
    ref = np.fft.irfftn(y, s=(n, n, nz), axes=axes)
    opts = FFTOptions(local_impl="xla")
    for strat in ("packed", "embed"):
        got = np.asarray(irfft3d(jnp.asarray(y), nz, opts=opts,
                                 strategy=strat))
        np.testing.assert_allclose(got, ref, atol=2e-6 * np.abs(ref).max(),
                                   err_msg=strat)


@pytest.mark.parametrize("norm", ["ortho", "backward", None])
def test_local_norm_roundtrips(norm, rng):
    """r2c norm semantics match numpy on both strategies (satellite:
    normalization coverage)."""
    x = rng.randn(8, 8, 16).astype(np.float32)
    np_norm = norm if norm is not None else "backward"
    ref = np.fft.rfftn(x, norm=np_norm)
    for strat in ("packed", "embed"):
        y = np.asarray(rfft3d(jnp.asarray(x), strategy=strat, norm=norm))
        np.testing.assert_allclose(y, ref, atol=3e-5 * np.abs(ref).max(),
                                   err_msg=f"{strat}/{norm}")
        xb = np.asarray(irfft3d(jnp.asarray(y), 16, strategy=strat,
                                norm=norm))
        np.testing.assert_allclose(xb, x, atol=2e-5,
                                   err_msg=f"{strat}/{norm}")


# --- packing primitives ------------------------------------------------------

def test_pack_unpack_two_for_one_identity(rng):
    """unpack(FFT(pack(x))) splits exactly into the two pencils' FFTs."""
    a = rng.randn(3, 16).astype(np.float32)
    b = rng.randn(3, 16).astype(np.float32)
    x = np.concatenate([a, b], axis=0)          # pair axis 0: halves
    c = packing.pack_two(jnp.asarray(x), 0)
    C = jnp.fft.fft(c, axis=-1)
    S = packing.unpack_two(C, 0, nh=9)
    np.testing.assert_allclose(np.asarray(S[:3]), np.fft.rfft(a, axis=-1),
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(S[3:]), np.fft.rfft(b, axis=-1),
                               atol=1e-4)


@pytest.mark.parametrize("folded", [True, False])
def test_repack_inverts_unpack(folded, rng):
    nz = 32
    x = rng.randn(6, nz).astype(np.float32)     # 3 pairs
    C = jnp.fft.fft(packing.pack_two(jnp.asarray(x), 0), axis=-1)
    S = packing.unpack_two(C, 0, nh=nz // 2 + 1, fold=folded)
    C2 = packing.repack_halves(S, 0, nz, folded=folded)
    np.testing.assert_allclose(np.asarray(C2), np.asarray(C), atol=1e-4)
    xb = packing.split_pairs(jnp.fft.ifft(C2, axis=-1), 0)
    np.testing.assert_allclose(np.asarray(xb), x, atol=1e-5)


# --- Pallas Hermitian kernels vs the jnp reference ---------------------------

@pytest.mark.parametrize("n", [16, 64, 256])
def test_hermitian_kernels_match_reference(n, rng):
    from repro.core.local_fft import from_planes, to_planes
    C = (rng.randn(8, 4, n) + 1j * rng.randn(8, 4, n)).astype(np.complex64)
    Cj = jnp.asarray(C)
    ref = packing.unpack_two(Cj, 1, fold=True)
    ker = packing.unpack_two_planes(to_planes(Cj), 2, use_pallas=True)
    np.testing.assert_allclose(np.asarray(from_planes(ker)), np.asarray(ref),
                               atol=1e-6)
    ref2 = packing.repack_halves(ref, 1, n, folded=True)
    ker2 = packing.repack_halves_planes(to_planes(ref), 2, n, use_pallas=True)
    np.testing.assert_allclose(np.asarray(from_planes(ker2)),
                               np.asarray(ref2), atol=1e-6)


@pytest.mark.parametrize("use_pallas", [False, True])
def test_planes_packing_steps_match_complex_forms(use_pallas, rng):
    """The schedule executor's planes forms of the packing steps (plane
    axis 0 ahead of the pair axis) equal the complex forms, through the
    Pallas plane kernels (interpreted here) and through the conversion
    at the op."""
    from repro.core.local_fft import from_planes, to_planes
    n = 16
    x = rng.randn(2, 6, n).astype(np.float32)           # pair axis 1
    c = packing.pack_two(jnp.asarray(x), 1)
    pc = packing.pack_two_planes(to_planes(jnp.asarray(x)), 2)
    np.testing.assert_array_equal(np.asarray(from_planes(pc)), np.asarray(c))
    C = jnp.fft.fft(c, axis=-1)
    S = packing.unpack_two(C, 1, fold=True)
    ps = packing.unpack_two_planes(to_planes(C), 2, use_pallas=use_pallas)
    np.testing.assert_allclose(np.asarray(from_planes(ps)), np.asarray(S),
                               atol=1e-6)
    C2 = packing.repack_halves(S, 1, n, folded=True)
    pc2 = packing.repack_halves_planes(to_planes(S), 2, n,
                                       use_pallas=use_pallas)
    np.testing.assert_allclose(np.asarray(from_planes(pc2)), np.asarray(C2),
                               atol=1e-6)
    xb = packing.split_pairs_planes(to_planes(c), 2)
    assert xb.shape == (1,) + x.shape
    np.testing.assert_array_equal(np.asarray(from_planes(xb)), x)


@pytest.mark.parametrize("batch", [(), (3,)])
@pytest.mark.parametrize("pair", [-2, -3])
@pytest.mark.parametrize("n,fold", [(16, True), (16, False), (15, False)])
def test_planes_unpack_repack_jnp_forms(n, fold, pair, batch, rng):
    """The plane-native jnp forms of the two-for-one split and its
    inverse equal the complex forms, folded and not (odd n has no
    Nyquist bin), pairing along y or x, under leading batch axes; and
    the split of a packed FFT is the two pencils' rfft."""
    from repro.core.local_fft import from_planes, to_planes
    shape = batch + (4, 6, n)
    x = rng.randn(*shape).astype(np.float32)
    C = jnp.fft.fft(packing.pack_two(jnp.asarray(x), pair), axis=-1)
    nh = n // 2 + 1
    S = packing.unpack_two(C, pair, nh=nh, fold=fold)
    ps = packing.unpack_two_planes(to_planes(C), pair, nh=nh, fold=fold)
    np.testing.assert_allclose(np.asarray(from_planes(ps)), np.asarray(S),
                               atol=1e-5)
    if not fold:
        np.testing.assert_allclose(np.asarray(S), np.fft.rfft(x, axis=-1),
                                   atol=1e-4)
    C2 = packing.repack_halves(S, pair, n, folded=fold)
    pc2 = packing.repack_halves_planes(to_planes(S), pair, n, folded=fold)
    np.testing.assert_allclose(np.asarray(from_planes(pc2)), np.asarray(C2),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(C2), np.asarray(C), atol=1e-4)


@pytest.mark.parametrize("norm", [None, "backward", "ortho", "none"])
@pytest.mark.parametrize("shape,impl", [
    ((8, 4, 15), ("xla", "matmul", "xla")),     # odd Nz: z by xla, y planes
    ((8, 9, 16), ("matmul", "xla", "matmul")),  # odd Ny: pairs along x
    ((3, 8, 4, 16), "matmul"),                  # a stack of three fields
    ((2, 2, 4, 8, 16), "matmul"),               # two leading axes
    ((2, 8, 4, 9), ("xla", "matmul", "xla")),   # odd Nz, stacked
])
def test_local_packed_planes_paths_match_rfftn(shape, impl, norm, rng):
    """The single-device packed r2c/c2r on planes against numpy over the
    last three axes, at odd Nz and odd Ny, on stacked fields, under
    every norm ("none" scales neither way)."""
    x = rng.randn(*shape).astype(np.float32)
    opts = FFTOptions(local_impl=impl)
    axes = (-3, -2, -1)
    ref = np.fft.rfftn(x, axes=axes,
                       norm="ortho" if norm == "ortho" else "backward")
    y = rfft3d(jnp.asarray(x), opts=opts, strategy="packed", norm=norm)
    assert y.shape == ref.shape and y.dtype == jnp.complex64
    np.testing.assert_allclose(np.asarray(y), ref,
                               atol=3e-6 * np.abs(ref).max())
    xb = np.asarray(irfft3d(y, shape[-1], opts=opts, strategy="packed",
                            norm=norm))
    want = x * math.prod(shape[-3:]) if norm == "none" else x
    np.testing.assert_allclose(xb, want, atol=2e-6 * np.abs(want).max())


def test_local_packed_fields_of_a_stack_come_out_as_alone(rng):
    """A stack through the batched entries: each field bitwise as the
    field alone (leading axes are dot batch dims, not longer rows)."""
    from repro.core import Croft3D
    plan = Croft3D((8, 8, 16), None, problem="r2c", strategy="packed")
    x = jnp.asarray(rng.randn(4, 8, 8, 16).astype(np.float32))
    many = plan.forward_batched(x)
    back = plan.inverse_batched(many)
    for b in range(4):
        np.testing.assert_array_equal(np.asarray(many[b]),
                                      np.asarray(plan.forward(x[b])))
        np.testing.assert_array_equal(np.asarray(back[b]),
                                      np.asarray(plan.inverse(many[b])))


def test_pallas_impl_end_to_end(rng):
    x = rng.randn(8, 8, 16).astype(np.float32)
    opts = FFTOptions(local_impl="pallas")
    y = np.asarray(rfft3d(jnp.asarray(x), opts=opts, strategy="packed"))
    ref = np.fft.rfftn(x)
    np.testing.assert_allclose(y, ref, atol=5e-5 * np.abs(ref).max())
    xb = np.asarray(irfft3d(jnp.asarray(y), 16, opts=opts, strategy="packed"))
    np.testing.assert_allclose(xb, x, atol=2e-5)


# --- per-stage local_impl ----------------------------------------------------

def test_fftoptions_stagewise_local_impl():
    o = FFTOptions(local_impl=("matmul", "stockham", "xla"))
    assert o.stage_impl(0) == "matmul" and o.stage_impl(2) == "xla"
    # homogeneous tuples collapse to the canonical scalar form
    assert FFTOptions(local_impl=("xla",) * 3).local_impl == "xla"
    # json round trip (lists re-tuple)
    o2 = FFTOptions(**json.loads(json.dumps(dataclasses.asdict(o))))
    assert o2 == o
    with pytest.raises(ValueError):
        FFTOptions(local_impl=("matmul", "xla"))


def test_stagewise_impl_local_3d(rng):
    from repro.core import local_fft as lf
    x = (rng.randn(8, 16, 32) + 1j * rng.randn(8, 16, 32)).astype(np.complex64)
    y = np.asarray(lf.fft3d_local(jnp.asarray(x),
                                  impl=("matmul", "stockham", "xla")))
    np.testing.assert_allclose(y, np.fft.fftn(x),
                               atol=2e-4 * np.abs(np.fft.fftn(x)).max())


def test_candidates_stagewise_and_r2c():
    het = tuning.enumerate_candidates((32, 32, 32), SIZES,
                                      heterogeneous_impls=True)
    tuples = [c for c in het if isinstance(c.opts.local_impl, tuple)]
    assert tuples and all(len(c.opts.local_impl) == 3 for c in tuples)
    assert all("-" in c.label for c in tuples)

    r2c = tuning.enumerate_candidates((32, 32, 32), SIZES, problem="r2c")
    strategies = {c.strategy for c in r2c}
    assert strategies == {"packed", "embed"}
    assert all(c.problem == "r2c" for c in r2c)
    # packed candidates only where the pipelines support them — pencil
    # (pair z-pencils) and, since the schedule refactor, slab (pair
    # x-lines); this divisible 32^3 problem must offer both
    packed_kinds = {c.decomp.kind for c in r2c if c.strategy == "packed"}
    assert packed_kinds == {"pencil", "slab"}
    for c in r2c:
        if c.strategy == "packed":
            assert real_lib.packed_unsupported_reason(
                (32, 32, 32), c.decomp, SIZES, c.opts) is None


def test_cost_model_packed_halves_roofline_terms():
    dec = Decomposition("pencil", ("data", "model"))
    opts = FFTOptions(output_layout="spectral")
    mk = lambda strat: tuning.Candidate(dec, opts, problem="r2c",
                                        strategy=strat)
    packed = tuning.analytic_cost((64,) * 3, mk("packed"), SIZES)
    embed = tuning.analytic_cost((64,) * 3, mk("embed"), SIZES)
    assert packed.flops == embed.flops / 2
    assert packed.local_bytes == embed.local_bytes / 2
    # 3 half-volume shuffles vs 2 full transposes
    assert packed.collective_bytes == 0.75 * embed.collective_bytes
    # at bandwidth-bound sizes packed dominates its embed counterpart,
    # and the model ranks the best pencil plan as a packed one (the
    # global winner may be a slab at low P, where one full-volume
    # transpose undercuts three half-volume shuffles — at scale the
    # P <= Nz slab wall leaves pencil-packed as the scalable choice)
    big_p = tuning.analytic_cost((256,) * 3, mk("packed"), SIZES)
    big_e = tuning.analytic_cost((256,) * 3, mk("embed"), SIZES)
    assert big_p.total_s < big_e.total_s
    r = tuning.tune((256,) * 3, axis_sizes=SIZES, mode="model", problem="r2c")
    assert r.problem == "r2c" and r.strategy in ("packed", "embed")
    pencil_rows = [row["label"] for row in r.ranked
                   if row["label"].startswith("pencil")]
    assert pencil_rows and pencil_rows[0].endswith("r2c-packed")


def test_stagewise_cost_uses_per_stage_efficiency():
    dec = Decomposition("pencil", ("data", "model"))
    fast = tuning.analytic_cost(
        (64,) * 3, tuning.Candidate(dec, FFTOptions(local_impl="matmul")),
        SIZES)
    mixed = tuning.analytic_cost(
        (64,) * 3, tuning.Candidate(
            dec, FFTOptions(local_impl=("matmul", "stockham", "matmul"))),
        SIZES)
    slow = tuning.analytic_cost(
        (64,) * 3, tuning.Candidate(dec, FFTOptions(local_impl="stockham")),
        SIZES)
    assert fast.compute_s < mixed.compute_s < slow.compute_s


# --- wisdom: problem dimension, strategy round trip, seed + CLI --------------

def test_wisdom_key_problem_dimension():
    k_c2c = tuning.wisdom_key((32,) * 3, SIZES, jnp.complex64, "cpu")
    k_r2c = tuning.wisdom_key((32,) * 3, SIZES, jnp.complex64, "cpu", "r2c")
    assert k_c2c != k_r2c and k_r2c.endswith("|r2c")
    assert k_c2c.count("|") == 3  # legacy four-field format preserved


def test_wisdom_entry_strategy_roundtrip(tmp_path):
    path = str(tmp_path / "w.json")
    cand = tuning.Candidate(Decomposition("pencil", ("data", "model")),
                            FFTOptions(output_layout="spectral",
                                       local_impl=("matmul", "xla", "xla")),
                            problem="r2c", strategy="packed")
    key = tuning.wisdom_key((32,) * 3, SIZES, jnp.complex64, "any", "r2c")
    w = tuning.Wisdom(path=path)
    w.record(key, tuning.WisdomEntry.from_candidate(cand, "measure",
                                                    measured_s=1e-3))
    w.save()
    got = tuning.Wisdom.load(path).lookup(key).candidate()
    assert got.problem == "r2c" and got.strategy == "packed"
    assert got.opts == cand.opts and got.decomp == cand.decomp


def test_wisdom_model_entries_newer_wins():
    """Merging an old wisdom file back in must not clobber fresher model
    entries (cost-model improvements propagate forward, not backward)."""
    cand_old = tuning.Candidate(Decomposition("slab", ("p",)), FFTOptions())
    cand_new = tuning.Candidate(Decomposition("pencil", ("a", "p")),
                                FFTOptions(overlap_k=4))
    old = tuning.WisdomEntry.from_candidate(cand_old, "model", model_s=1e-3)
    old.created = 100.0
    new = tuning.WisdomEntry.from_candidate(cand_new, "model", model_s=2e-3)
    new.created = 200.0
    w = tuning.Wisdom()
    w.record("k", new)
    w.record("k", old)          # stale entry arrives second
    assert w.lookup("k").created == 200.0
    # but a measured entry still beats any model entry, old or new
    meas = tuning.WisdomEntry.from_candidate(cand_old, "measure",
                                             measured_s=1e-3)
    w.record("k", meas)
    w.record("k", new)
    assert w.lookup("k").measured_s == 1e-3


def test_seed_wisdom_ships_and_cli_merges(tmp_path):
    seed = tuning.load_seed()
    assert len(seed) > 0
    assert any(k.endswith("|r2c") for k in seed.entries)
    # every shipped entry deserializes to a valid candidate
    for e in seed.entries.values():
        e.candidate()
    out = str(tmp_path / "merged.json")
    env = dict(os.environ, PYTHONPATH=SRC + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    proc = subprocess.run(
        [sys.executable, "-m", "repro.tuning.wisdom", "merge", out, "--seed"],
        env=env, capture_output=True, text=True)
    assert proc.returncode == 0, proc.stderr
    assert len(tuning.Wisdom.load(out)) == len(seed)


# --- multi-device: packed vs numpy, guard, tuned r2c plan --------------------

def test_distributed_r2c_strategies_and_guard():
    run_multidevice("""
import numpy as np, jax, jax.numpy as jnp
from repro.core import Croft3D, Decomposition, FFTOptions
rng = np.random.RandomState(42)
mesh = jax.make_mesh((2,4), ("data","model"),
                     axis_types=(jax.sharding.AxisType.Auto,)*2)
dec = Decomposition("pencil", ("data","model"))

def check(shape, opts, strat, tag):
    x = rng.randn(*shape).astype(np.float32)
    ref = np.fft.rfftn(x)
    plan = Croft3D(shape, mesh, dec, opts, problem="r2c", strategy=strat)
    assert plan.strategy == strat
    xd = jax.device_put(jnp.asarray(x), plan.input_sharding)
    y = plan.forward(xd)
    assert y.shape == ref.shape, (y.shape, ref.shape)
    err = float(jnp.max(jnp.abs(y - ref))) / np.abs(ref).max()
    xb = plan.inverse(y)
    assert not jnp.iscomplexobj(xb) or strat == "embed"
    rerr = float(jnp.max(jnp.abs(xb - x)))
    assert err < 1e-5, (tag, err)
    assert rerr < 1e-4, (tag, rerr)
    print("OK", tag, err, rerr)

N = 32
for strat in ("packed", "embed"):
    check((N,N,N), FFTOptions(), strat, strat)
    check((N,N,N), FFTOptions(overlap_k=1), strat, strat + "-k1")
check((N,N,N), FFTOptions(local_impl=("matmul","stockham","xla")),
      "packed", "packed-stagewise")
# guard: natural-layout embed slice where Nh % shard != 0 (Nz=8, Pz=4)
check((64, 16, 8), FFTOptions(), "embed", "embed-guard-odd-shard")
# spectral-layout embed (z already local: plain slice)
check((N,N,N), FFTOptions(output_layout="spectral"), "embed", "embed-spectral")
# packed refuses unsupported problems with a reason: (32, 4, 32) is
# c2c-valid but leaves one z-pencil per device — nothing to pair
try:
    Croft3D((N, 4, N), mesh, dec, FFTOptions(), problem="r2c",
            strategy="packed")
    raise SystemExit("packed should have been rejected for Ny=4")
except ValueError as e:
    assert "packed" in str(e)
    print("OK packed-rejection:", e)
# auto on the same problem falls back to embed
plan = Croft3D((N, 4, N), mesh, dec, FFTOptions(), problem="r2c")
assert plan.strategy == "embed"
print("OK auto-fallback")
# output_sharding keeps the odd-sized Nh axis local for every kind —
# including cell, whose spectral spec shards z; filters placed with it
# must be shardable (Nh=5 would not tile a z shard)
mesh222 = jax.make_mesh((2,2,2), ("a","b","c"),
                        axis_types=(jax.sharding.AxisType.Auto,)*3)
cplan = Croft3D((8, 8, 8), mesh222, Decomposition("cell", ("a","b","c")),
                FFTOptions(), problem="r2c")
assert cplan.output_sharding.spec[2] is None, cplan.output_sharding.spec
filt = jax.device_put(jnp.ones((8, 8, 5), jnp.complex64),
                      cplan.output_sharding)
xc = rng.randn(8, 8, 8).astype(np.float32)
yc = cplan.forward(jax.device_put(jnp.asarray(xc), cplan.input_sharding))
err = np.abs(np.asarray(yc) - np.fft.rfftn(xc)).max()
assert err < 1e-4, err
print("OK cell r2c + z-local output sharding")
""", timeout=900)


def test_distributed_packed_slab_and_norm():
    """The packed-slab strategy (pair x-lines, one half-volume z<->x
    transpose) on a 1-axis mesh: numpy parity, exact inverse, norm
    round trips, and the auto-resolution picking it."""
    run_multidevice("""
import numpy as np, jax, jax.numpy as jnp
from repro.core import Croft3D, Decomposition, FFTOptions
from repro.core.rfft import rfft3d, irfft3d
from jax.sharding import NamedSharding
rng = np.random.RandomState(5)
N = 32
mesh = jax.make_mesh((8,), ("p",), axis_types=(jax.sharding.AxisType.Auto,))
dec = Decomposition("slab", ("p",))
x = rng.randn(N, N, N).astype(np.float32)
ref = np.fft.rfftn(x)
plan = Croft3D((N,N,N), mesh, dec, FFTOptions(), problem="r2c")
assert plan.strategy == "packed"   # auto resolves to the slab pipeline
xd = jax.device_put(jnp.asarray(x), plan.input_sharding)
y = plan.forward(xd)
err = float(jnp.max(jnp.abs(y - ref))) / np.abs(ref).max()
xb = plan.inverse(y)
rerr = float(jnp.max(jnp.abs(xb - x)))
assert err < 1e-5, err
assert rerr < 1e-4, rerr
print("OK packed-slab", err, rerr)
# K=1 and per-stage impls
for opts, tag in [(FFTOptions(overlap_k=1), "k1"),
                  (FFTOptions(local_impl=("matmul","stockham","xla")),
                   "stagewise")]:
    p2 = Croft3D((N,N,N), mesh, dec, opts, problem="r2c", strategy="packed")
    y2 = p2.forward(jax.device_put(jnp.asarray(x), p2.input_sharding))
    e2 = float(jnp.max(jnp.abs(y2 - ref))) / np.abs(ref).max()
    assert e2 < 1e-4, (tag, e2)
    print("OK packed-slab", tag, e2)
# norm round trips through the distributed packed pipelines
sh = NamedSharding(mesh, dec.spectral_spec())
for norm in ("ortho", "backward"):
    yn = rfft3d(jax.device_put(jnp.asarray(x), sh), mesh, dec,
                FFTOptions(), strategy="packed", norm=norm)
    refn = np.fft.rfftn(x, norm=norm)
    en = float(jnp.max(jnp.abs(yn - refn))) / np.abs(refn).max()
    xn = irfft3d(yn, N, mesh, dec, FFTOptions(), strategy="packed",
                 norm=norm)
    rn = float(jnp.max(jnp.abs(xn - x)))
    assert en < 1e-5 and rn < 1e-4, (norm, en, rn)
    print("OK packed-slab norm", norm, en, rn)
# unpairable local Nx is rejected with a reason
try:
    Croft3D((8, N, N), mesh, dec, FFTOptions(), problem="r2c",
            strategy="packed")
    raise SystemExit("packed-slab should reject Nx/P == 1")
except ValueError as e:
    assert "pair" in str(e)
    print("OK packed-slab rejection:", e)
""", timeout=900)


def test_distributed_r2c_float64_and_tuned():
    run_multidevice("""
import jax
jax.config.update("jax_enable_x64", True)
import numpy as np, jax.numpy as jnp
from repro.core import Croft3D, Decomposition, FFTOptions
mesh = jax.make_mesh((2,4), ("y","z"),
                     axis_types=(jax.sharding.AxisType.Auto,)*2)
rng = np.random.RandomState(7)
N = 32
x = rng.randn(N,N,N)
ref = np.fft.rfftn(x)
plan = Croft3D((N,N,N), mesh, Decomposition("pencil", ("y","z")),
               FFTOptions(), dtype=jnp.complex128, problem="r2c",
               strategy="packed")
assert plan.input_dtype == jnp.float64
xd = jax.device_put(jnp.asarray(x), plan.input_sharding)
y = plan.forward(xd)
err = float(jnp.max(jnp.abs(y - ref))) / np.abs(ref).max()
assert err < 1e-12, err
xb = plan.inverse(y)
assert xb.dtype == jnp.float64
rerr = float(jnp.max(jnp.abs(xb - x)))
assert rerr < 1e-11, rerr
print("c128 packed fwd relerr", err, "roundtrip", rerr)

# tuned r2c plan: planner measures real-input candidates end to end
plan2 = Croft3D.tuned((N,N,N), mesh, mode="measure", problem="r2c",
                      top_k=2, measure_iters=2)
print("tuned:", plan2.tune_result.summary())
assert plan2.tune_result.problem == "r2c"
assert plan2.strategy in ("packed", "embed")
x32 = x.astype(np.float64)
y2 = plan2.forward(jax.device_put(jnp.asarray(x32), plan2.input_sharding))
err2 = float(jnp.max(jnp.abs(y2 - ref))) / np.abs(ref).max()
assert err2 < 1e-5, err2
print("OK tuned r2c", err2)
""", timeout=900)


def test_batched_packed_r2c_native_and_vmapped_measure():
    """Leading batch axes ride the packed pipeline natively (one schedule,
    batched collectives, one amortized DC/Nyquist unfold — no per-field
    vmap dispatch), vmap still works on top, and mode="measure" with
    batch=B times the vmapped transform (the ROADMAP follow-on)."""
    run_multidevice("""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core import Croft3D, Decomposition, FFTOptions
from repro import tuning
mesh = jax.make_mesh((2,4), ("y","z"),
                     axis_types=(jax.sharding.AxisType.Auto,)*2)
dec = Decomposition("pencil", ("y","z"))
N, B = 32, 3
rng = np.random.RandomState(11)
xb = rng.randn(B, N, N, N).astype(np.float32)
ref = np.stack([np.fft.rfftn(xb[i]) for i in range(B)])
plan = Croft3D((N,N,N), mesh, dec, FFTOptions(), problem="r2c",
               strategy="packed")
sh = NamedSharding(mesh, P(None, *plan.input_sharding.spec))
xd = jax.device_put(jnp.asarray(xb), sh)

# native leading batch axis: one transform call over (B, Nx, Ny, Nz)
y = plan.forward(xd)
assert y.shape == (B, N, N, N//2 + 1), y.shape
err = float(jnp.max(jnp.abs(y - ref))) / np.abs(ref).max()
assert err < 1e-5, err
xb_back = plan.inverse(y)
rerr = float(jnp.max(jnp.abs(xb_back - xb)))
assert rerr < 1e-4, rerr

# the batched call compiles to the same collective COUNT as one field:
# the batch rides inside each launch instead of multiplying launches
from repro.launch import hlo_cost
def coll_count(fn, spec):
    c = jax.jit(fn).lower(spec).compile()
    a = hlo_cost.analyze(c.as_text())
    return sum(v["count"] for v in a.collectives.values())
s1 = jax.ShapeDtypeStruct((N,N,N), jnp.float32,
                          sharding=plan.input_sharding)
sB = jax.ShapeDtypeStruct((B,N,N,N), jnp.float32, sharding=sh)
n1, nB = coll_count(plan.forward, s1), coll_count(plan.forward, sB)
assert n1 == nB, (n1, nB)

# vmap on top of the native path still matches
yv = jax.jit(jax.vmap(plan.forward))(xd)
assert float(jnp.max(jnp.abs(yv - ref))) / np.abs(ref).max() < 1e-5

# mode="measure" with batch=B builds and times vmapped candidates
res = tuning.tune((N,N,N), mesh, mode="measure", problem="r2c",
                  batch=B, top_k=1, measure_iters=2, measure_warmup=1)
assert res.measured_s is not None and res.measured_s > 0
assert res.key.endswith("|b%d" % B), res.key
t = tuning.time_forward(plan, warmup=1, iters=2, batch=B)
assert t > 0
print("OK batched packed r2c", err, "colls", n1, "measured", res.measured_s)
""", timeout=900)


def test_fused_filter_epilogue_moves_fewer_bytes_than_separate_multiply():
    """The k-space multiply fused as the packed r2c schedule's epilogue
    compiles to strictly fewer HLO bytes than the forward plus a
    separate multiply: one spectrum round trip fewer, no extra copy."""
    run_multidevice("""
import jax, jax.numpy as jnp
from repro.core import Croft3D, Decomposition
from repro.launch import hlo_cost
from repro.launch.mesh import make_mesh
N = 64
mesh = make_mesh((2, 4), ("y", "z"))
plan = Croft3D((N, N, N), mesh, Decomposition("pencil", ("y", "z")),
               problem="r2c", strategy="packed")
x = jax.ShapeDtypeStruct(plan.shape, plan.input_dtype,
                         sharding=plan.input_sharding)
h = jax.ShapeDtypeStruct(plan.spectrum_shape, jnp.complex64,
                         sharding=plan.output_sharding)
def hlo_bytes(lowered):
    return hlo_cost.analyze(lowered.compile().as_text()).bytes
b_fwd = hlo_bytes(plan._fwd.lower(x))
b_mul = hlo_bytes(jax.jit(lambda y, hh: y * hh).lower(h, h))
b_fused = hlo_bytes(plan._filtered_fn().lower(x, h))
assert b_fused < b_fwd + b_mul, (b_fused, b_fwd, b_mul)
print("OK", b_fused, b_fwd + b_mul)
""")
