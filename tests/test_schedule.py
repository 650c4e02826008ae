"""Stage-schedule IR: golden snapshots of the built pipelines, symbolic
layout propagation, effective-K reporting, cost-model derivation, the
batch wisdom-key dimension, and the pairwise-transpose rejections.

The golden strings pin the *stage structure* of every standard
decomposition: a refactor that changes what the executor would run (stage
order, transpose axes, chunk axes, pack/unpack placement) fails here
loudly instead of silently shifting numerics or cost-model rankings.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from conftest import run_multidevice
from repro.core import Decomposition, FFTOptions
from repro.core import schedule as schedule_lib
from repro.core.distributed import build_schedule
from repro.grad import adjoint_schedule
from repro.real.pipeline import build_packed_forward, build_packed_inverse
from repro import tuning

SIZES = {"data": 2, "model": 4}
PENCIL = Decomposition("pencil", ("data", "model"))
SLAB = Decomposition("slab", ("p",))
CELL = Decomposition("cell", ("a", "b", "c"))


# --- golden snapshots --------------------------------------------------------

GOLDEN = {
    "pencil-natural": """\
schedule pencil/c2c/natural sign=-1
  in : C(Nx, Ny/data, Nz/model)
  0 x-fft+xy: fft[x]@s0 | a2a[data] split=0 concat=1 chunk=2 -> C(Nx/data, Ny, Nz/model)
  1 y-fft+yz: fft[y]@s1 | a2a[model] split=1 concat=2 chunk=0 -> C(Nx/data, Ny/model, Nz)
  2 z-fft: fft[z]@s2 -> C(Nx/data, Ny/model, Nz)
  3 restore-yz: a2a[model] split=2 concat=1 chunk=0 -> C(Nx/data, Ny, Nz/model)
  4 restore-xy: a2a[data] split=1 concat=0 chunk=2 -> C(Nx, Ny/data, Nz/model)
  out: C(Nx, Ny/data, Nz/model)""",
    "pencil-spectral": """\
schedule pencil/c2c/spectral sign=-1
  in : C(Nx, Ny/data, Nz/model)
  0 x-fft+xy: fft[x]@s0 | a2a[data] split=0 concat=1 chunk=2 -> C(Nx/data, Ny, Nz/model)
  1 y-fft+yz: fft[y]@s1 | a2a[model] split=1 concat=2 chunk=0 -> C(Nx/data, Ny/model, Nz)
  2 z-fft: fft[z]@s2 -> C(Nx/data, Ny/model, Nz)
  out: C(Nx/data, Ny/model, Nz)""",
    "pencil-from-spectral": """\
schedule pencil/c2c/from-spectral sign=+1
  in : C(Nx/data, Ny/model, Nz)
  0 z-fft+zy: fft[z]@s0 | a2a[model] split=2 concat=1 chunk=0 -> C(Nx/data, Ny, Nz/model)
  1 y-fft+yx: fft[y]@s1 | a2a[data] split=1 concat=0 chunk=2 -> C(Nx, Ny/data, Nz/model)
  2 x-fft: fft[x]@s2 -> C(Nx, Ny/data, Nz/model)
  out: C(Nx, Ny/data, Nz/model)""",
    "slab-natural": """\
schedule slab/c2c/natural sign=-1
  in : C(Nx, Ny, Nz/p)
  0 y-fft: fft[y]@s0 -> C(Nx, Ny, Nz/p)
  1 x-fft+xz: fft[x]@s1 | a2a[p] split=0 concat=2 chunk=1 -> C(Nx/p, Ny, Nz)
  2 z-fft: fft[z]@s2 -> C(Nx/p, Ny, Nz)
  3 restore-zx: a2a[p] split=2 concat=0 chunk=1 -> C(Nx, Ny, Nz/p)
  out: C(Nx, Ny, Nz/p)""",
    "cell-natural": """\
schedule cell/c2c/natural sign=-1
  in : C(Nx/a, Ny/b, Nz/c)
  0 regroup-x: a2a[a] split=1 concat=0 chunk=2 -> C(Nx, Ny/b/a, Nz/c)
  1 x-fft+xy: fft[x]@s0 | a2a[b+a] split=0 concat=1 chunk=2 -> C(Nx/b/a, Ny, Nz/c)
  2 y-fft+yz: fft[y]@s1 | a2a[c] split=1 concat=2 chunk=0 -> C(Nx/b/a, Ny/c, Nz)
  3 z-fft: fft[z]@s2 -> C(Nx/b/a, Ny/c, Nz)
  4 restore-yz: a2a[c] split=2 concat=1 chunk=0 -> C(Nx/b/a, Ny, Nz/c)
  5 restore-xy: a2a[b+a] split=1 concat=0 chunk=2 -> C(Nx, Ny/b/a, Nz/c)
  6 scatter-x: a2a[a] split=0 concat=1 chunk=2 -> C(Nx/a, Ny/b, Nz/c)
  out: C(Nx/a, Ny/b, Nz/c)""",
    "packed-pencil-fwd": """\
schedule pencil/r2c/packed sign=-1
  in : R(Nx/data, Ny/model, Nz)
  0 pack+z-rfft+zy: pack2[y] | fft[z]@s0 | unpack2[y] | a2a[model] split=2 concat=1 chunk=0 -> C(Nx/data, Ny, Nz:2/model)
  1 y-fft+yx: fft[y]@s1 | a2a[data] split=1 concat=0 chunk=2 -> C(Nx, Ny/data, Nz:2/model)
  2 x-fft: fft[x]@s2 -> C(Nx, Ny/data, Nz:2/model)
  + reshard z-localize: C(Nx, Ny/data, Nz:2/model) (one fused all-to-all)
  out: C(Nx, Ny/data, Nz:2/model)""",
    "packed-pencil-inv": """\
schedule pencil/c2r/packed sign=+1
  in : C(Nx, Ny/data, Nz:2/model)
  0 x-ifft+xy: fft[x]@s0 | a2a[data] split=0 concat=1 chunk=2 -> C(Nx/data, Ny, Nz:2/model)
  1 y-ifft+yz: fft[y]@s1 | a2a[model] split=1 concat=2 chunk=0 -> C(Nx/data, Ny/model, Nz:2)
  2 repack+z-ifft+split: repack2[y] | fft[z]@s2 | split2[y] -> R(Nx/data, Ny/model, Nz)
  + reshard x-localize: C(Nx, Ny/data, Nz:2/model) (one fused all-to-all)
  out: R(Nx/data, Ny/model, Nz)""",
    "packed-slab-fwd": """\
schedule slab/r2c/packed sign=-1
  in : R(Nx/p, Ny, Nz)
  0 pack+z-rfft+zx: pack2[x] | fft[z]@s0 | unpack2[x] | a2a[p] split=2 concat=0 chunk=1 -> C(Nx, Ny, Nz:2/p)
  1 y-fft: fft[y]@s1 -> C(Nx, Ny, Nz:2/p)
  2 x-fft: fft[x]@s2 -> C(Nx, Ny, Nz:2/p)
  + reshard z-localize: C(Nx, Ny, Nz:2/p) (one fused all-to-all)
  out: C(Nx, Ny, Nz:2/p)""",
    "packed-slab-inv": """\
schedule slab/c2r/packed sign=+1
  in : C(Nx, Ny, Nz:2/p)
  0 x-ifft+xz: fft[x]@s0 | a2a[p] split=0 concat=2 chunk=1 -> C(Nx/p, Ny, Nz:2)
  1 y-ifft: fft[y]@s1 -> C(Nx/p, Ny, Nz:2)
  2 repack+z-ifft+split: repack2[x] | fft[z]@s2 | split2[x] -> R(Nx/p, Ny, Nz)
  + reshard x-localize: C(Nx, Ny, Nz:2/p) (one fused all-to-all)
  out: R(Nx/p, Ny, Nz)""",
    # adjoint schedules (repro.grad): the backward pass of each pinned
    # forward is itself a pinned schedule — stage order reversed, each
    # transpose's split/concat swapped, each packed op replaced by its
    # explicit transpose.  A refactor that silently changes what the
    # training backward runs fails here, same as a forward change.
    "adj-pencil-natural": """\
schedule pencil/c2c/natural^T sign=-1
  in : C(Nx, Ny/data, Nz/model)
  0 adj-comm-restore-xy: a2a[data] split=0 concat=1 chunk=2 -> C(Nx/data, Ny, Nz/model)
  1 adj-comm-restore-yz: a2a[model] split=1 concat=2 chunk=0 -> C(Nx/data, Ny/model, Nz)
  2 adj-z-fft: fft[z]@s0 | a2a[model] split=2 concat=1 chunk=0 -> C(Nx/data, Ny, Nz/model)
  3 adj-y-fft+yz: fft[y]@s1 | a2a[data] split=1 concat=0 chunk=2 -> C(Nx, Ny/data, Nz/model)
  4 adj-x-fft+xy: fft[x]@s2 -> C(Nx, Ny/data, Nz/model)
  out: C(Nx, Ny/data, Nz/model)""",
    "adj-pencil-spectral": """\
schedule pencil/c2c/spectral^T sign=-1
  in : C(Nx/data, Ny/model, Nz)
  0 adj-z-fft: fft[z]@s0 | a2a[model] split=2 concat=1 chunk=0 -> C(Nx/data, Ny, Nz/model)
  1 adj-y-fft+yz: fft[y]@s1 | a2a[data] split=1 concat=0 chunk=2 -> C(Nx, Ny/data, Nz/model)
  2 adj-x-fft+xy: fft[x]@s2 -> C(Nx, Ny/data, Nz/model)
  out: C(Nx, Ny/data, Nz/model)""",
    "adj-packed-pencil-fwd": """\
schedule pencil/r2c/packed^T sign=-1
  in : C(Nx, Ny/data, Nz:2/model)
  0 adj-x-fft: fft[x]@s0 | a2a[data] split=0 concat=1 chunk=2 -> C(Nx/data, Ny, Nz:2/model)
  1 adj-y-fft+yx: fft[y]@s1 | a2a[model] split=1 concat=2 chunk=0 -> C(Nx/data, Ny/model, Nz:2)
  2 adj-pack+z-rfft+zy: unpack2T[y] | fft[z]@s2 | pack2T[y] -> R(Nx/data, Ny/model, Nz)
  + reshard adj-z-localize: C(Nx, Ny/data, Nz:2/model) (one fused all-to-all)
  out: R(Nx/data, Ny/model, Nz)""",
    "adj-packed-slab-fwd": """\
schedule slab/r2c/packed^T sign=-1
  in : C(Nx, Ny, Nz:2/p)
  0 adj-x-fft: fft[x]@s0 -> C(Nx, Ny, Nz:2/p)
  1 adj-y-fft: fft[y]@s1 -> C(Nx, Ny, Nz:2/p)
  2 adj-comm-pack+z-rfft+zx: a2a[p] split=0 concat=2 chunk=1 -> C(Nx/p, Ny, Nz:2)
  3 adj-pack+z-rfft+zx: unpack2T[x] | fft[z]@s2 | pack2T[x] -> R(Nx/p, Ny, Nz)
  + reshard adj-z-localize: C(Nx, Ny, Nz:2/p) (one fused all-to-all)
  out: R(Nx/p, Ny, Nz)""",
}


def _built():
    return {
        "pencil-natural": build_schedule(PENCIL, FFTOptions()),
        "pencil-spectral": build_schedule(
            PENCIL, FFTOptions(output_layout="spectral")),
        "pencil-from-spectral": build_schedule(
            PENCIL, FFTOptions(output_layout="spectral"), sign=+1),
        "slab-natural": build_schedule(SLAB, FFTOptions()),
        "cell-natural": build_schedule(CELL, FFTOptions()),
        "packed-pencil-fwd": build_packed_forward(PENCIL),
        "packed-pencil-inv": build_packed_inverse(PENCIL, 32),
        "packed-slab-fwd": build_packed_forward(SLAB),
        "packed-slab-inv": build_packed_inverse(SLAB, 32),
        "adj-pencil-natural": adjoint_schedule(
            build_schedule(PENCIL, FFTOptions())),
        "adj-pencil-spectral": adjoint_schedule(
            build_schedule(PENCIL, FFTOptions(output_layout="spectral"))),
        "adj-packed-pencil-fwd": adjoint_schedule(
            build_packed_forward(PENCIL)),
        "adj-packed-slab-fwd": adjoint_schedule(build_packed_forward(SLAB)),
    }


@pytest.mark.parametrize("key", sorted(GOLDEN))
def test_golden_schedules(key):
    assert _built()[key].describe() == GOLDEN[key], (
        f"stage structure of {key} changed — if intentional, update the "
        "golden snapshot AND re-verify numerics + cost-model rankings")


# --- symbolic layouts --------------------------------------------------------

def test_layout_specs_match_decomposition():
    for dec in (PENCIL, SLAB, CELL, Decomposition("pencil",
                                                  (("a", "b"), "c"))):
        assert (schedule_lib.layout_for(dec, "natural").partition_spec()
                == dec.partition_spec())
        assert (schedule_lib.layout_for(dec, "spectral").partition_spec()
                == dec.spectral_spec())
    # schedules restore the layouts the shard_map wrappers advertise
    sched = build_schedule(PENCIL, FFTOptions())
    assert sched.layout_in.partition_spec() == PENCIL.partition_spec()
    assert sched.layout_out.partition_spec() == PENCIL.partition_spec()
    spec = build_schedule(PENCIL, FFTOptions(output_layout="spectral"))
    assert spec.layout_out.partition_spec() == PENCIL.spectral_spec()


def test_layout_local_shapes_and_bytes():
    sched = build_packed_forward(PENCIL)
    shape = (32, 32, 32)
    # real input: same byte count as the Nz/2 complex spectrum it becomes
    assert sched.layout_in.local_shape(shape, SIZES) == (16, 8, 32)
    assert sched.layout_in.bytes(shape, SIZES, 8) == 16 * 8 * 32 * 4
    assert sched.layout_out.local_shape(shape, SIZES) == (32, 16, 4)
    assert sched.layout_out.bytes(shape, SIZES, 8) == 32 * 16 * 4 * 8


def test_builder_errors_are_loud():
    with pytest.raises(schedule_lib.ScheduleError):
        # FFT along a sharded axis must fail at build time, not trace time
        schedule_lib.Schedule(
            "bad", -1, schedule_lib.layout_for(PENCIL, "natural"),
            (schedule_lib.Stage("bad", fft_axis=1),))
    with pytest.raises(schedule_lib.ScheduleError):
        # transposing over a communicator the concat dim is not sharded by
        schedule_lib.Schedule(
            "bad", -1, schedule_lib.layout_for(PENCIL, "natural"),
            (schedule_lib.Stage("bad", comm_axis="model", split_axis=0,
                                concat_axis=1),))


# --- effective-K reporting (the executor's chunk-indivisible fallback) -------

def test_effective_k_reports_fallback():
    sched = build_schedule(PENCIL, FFTOptions())
    shape = (32, 32, 32)
    # divisible: every comm stage runs at the requested K
    assert sched.effective_k(shape, SIZES, 2) == (2, 2, 2, 2)
    assert sched.effective_k(shape, SIZES, 4) == (4, 4, 4, 4)
    # K=16 fits only the stages chunked along x (local extent 16), not
    # those chunked along z (local 8) — per-stage, not all-or-nothing
    assert sched.effective_k(shape, SIZES, 16) == (1, 16, 16, 1)
    cell = build_schedule(CELL, FFTOptions())
    abc = {"a": 2, "b": 2, "c": 2}
    assert cell.effective_k((8, 8, 8), abc, 3) == (1,) * 6
    assert cell.effective_k((8, 8, 8), abc, 2) == (2,) * 6


def test_chunk_fallback_matches_k1_numerics():
    """K not dividing the chunk axes must silently fall back per stage and
    still produce the identical transform (cell validate does not gate
    overlap chunking, so this path is reachable)."""
    run_multidevice("""
import numpy as np, jax, jax.numpy as jnp
from repro.core import Croft3D, Decomposition, FFTOptions
from repro.core.distributed import build_schedule
mesh = jax.make_mesh((2,2,2), ("a","b","c"),
                     axis_types=(jax.sharding.AxisType.Auto,)*3)
dec = Decomposition("cell", ("a","b","c"))
N = 8
sched = build_schedule(dec, FFTOptions(overlap_k=3))
ks = sched.effective_k((N,N,N), dict(mesh.shape), 3)
assert ks == (1,)*6, ks          # every stage falls back
rng = np.random.RandomState(0)
x = (rng.randn(N,N,N) + 1j*rng.randn(N,N,N)).astype(np.complex64)
outs = {}
for k in (1, 3):
    plan = Croft3D((N,N,N), mesh, dec, FFTOptions(overlap_k=k))
    xd = jax.device_put(jnp.asarray(x), plan.input_sharding)
    outs[k] = np.asarray(plan.forward(xd))
assert np.array_equal(outs[1], outs[3])   # identical op graph -> bitwise
ref = np.fft.fftn(x)
assert np.max(np.abs(outs[3] - ref)) / np.abs(ref).max() < 1e-5
print("OK chunk fallback == K=1")
""")


# --- cost model walks the schedule ------------------------------------------

def test_cost_model_counts_derive_from_schedule():
    shape = (32, 32, 32)
    mk = lambda dec, **kw: tuning.Candidate(dec, FFTOptions(**kw))
    for cand, n_transposes in [
            (mk(PENCIL), 4),
            (mk(PENCIL, output_layout="spectral"), 2),
            (mk(Decomposition("slab", ("model",))), 2),
            (tuning.Candidate(PENCIL, FFTOptions(output_layout="spectral"),
                              problem="r2c", strategy="packed"), 3),
    ]:
        from repro.tuning.cost_model import schedule_for
        sched = schedule_for(shape, cand)
        assert sched.transpose_count() == n_transposes
        events = sched.comm_events(shape, SIZES)
        assert len(events) == n_transposes
        cost = tuning.analytic_cost(shape, cand, SIZES)
        assert cost.collective_bytes == float(
            sum(ev["bytes"] for ev in events))
    # cell: regroup + pencil natural (4) + scatter = 6 transposes (the
    # old hand-derived model charged 8 — the schedule knows better)
    from repro.tuning.cost_model import schedule_for
    cell = tuning.Candidate(CELL, FFTOptions())
    assert schedule_for(shape, cell).transpose_count() == 6


def test_cost_model_packed_slab_candidate():
    """The packed-slab strategy is enumerated on 1-axis meshes, halves the
    volume terms, and is modeled cheaper than the embedding at scale.

    Unlike the pencil case, packed-slab does not halve *collective*
    bytes (one half-volume transpose + the half-volume z-localizing
    reshard equal the embedding's single full-volume transpose), so its
    win comes from compute/memory — latency-dominated small shapes stay
    with the embedding, exactly what a schedule-derived model shows.
    """
    sizes = {"p": 8}
    cands = tuning.enumerate_candidates((64,) * 3, sizes, problem="r2c")
    packed = [c for c in cands if c.strategy == "packed"]
    assert packed and all(c.decomp.kind == "slab" for c in packed)
    slab = Decomposition("slab", ("p",))
    mk = lambda strat: tuning.Candidate(
        slab, FFTOptions(output_layout="spectral"), problem="r2c",
        strategy=strat)
    p = tuning.analytic_cost((64,) * 3, mk("packed"), sizes)
    e = tuning.analytic_cost((64,) * 3, mk("embed"), sizes)
    assert p.flops == e.flops / 2
    assert p.local_bytes == e.local_bytes / 2
    assert p.collective_bytes == e.collective_bytes
    big_p = tuning.analytic_cost((256,) * 3, mk("packed"), sizes)
    big_e = tuning.analytic_cost((256,) * 3, mk("embed"), sizes)
    assert big_p.total_s < big_e.total_s


def test_cost_model_chunk_fallback_disables_overlap_bonus():
    """A K that no stage can honor must be modeled as unoverlapped."""
    big = (256, 256, 256)
    dec = PENCIL
    k1 = tuning.analytic_cost(big, tuning.Candidate(
        dec, FFTOptions(overlap_k=1)), SIZES)
    k2 = tuning.analytic_cost(big, tuning.Candidate(
        dec, FFTOptions(overlap_k=2)), SIZES)
    # 3 does not divide the 64/128-sized chunk extents: falls back
    k3 = tuning.analytic_cost(big, tuning.Candidate(
        dec, FFTOptions(overlap_k=3)), SIZES)
    assert k2.total_s < k1.total_s
    assert k3.total_s == pytest.approx(k1.total_s)


def test_cost_model_batch_scales_volume_not_launches():
    cand = tuning.Candidate(PENCIL, FFTOptions())
    b1 = tuning.analytic_cost((32,) * 3, cand, SIZES, batch=1)
    b8 = tuning.analytic_cost((32,) * 3, cand, SIZES, batch=8)
    assert b8.flops == 8 * b1.flops
    assert b8.local_bytes == 8 * b1.local_bytes
    assert b8.collective_bytes == 8 * b1.collective_bytes
    assert b8.n_collectives == b1.n_collectives
    assert b8.latency_s == b1.latency_s


# --- wisdom batch dimension --------------------------------------------------

def test_wisdom_key_batch_dimension():
    k1 = tuning.wisdom_key((32,) * 3, SIZES, jnp.complex64, "cpu")
    kb = tuning.wisdom_key((32,) * 3, SIZES, jnp.complex64, "cpu", batch=8)
    assert kb == k1 + "|b8"
    # batch=1 keeps the legacy format: wisdom written before the batch
    # dimension existed still hits ("old keys parse as b1")
    assert tuning.wisdom_key((32,) * 3, SIZES, jnp.complex64, "cpu",
                             batch=1) == k1
    kr = tuning.wisdom_key((32,) * 3, SIZES, jnp.complex64, "cpu", "r2c", 4)
    assert kr.endswith("|r2c|b4")


def test_tune_batch_threads_through(tmp_path):
    path = str(tmp_path / "w.json")
    r1 = tuning.tune((32,) * 3, axis_sizes=SIZES, mode="model",
                     wisdom_path=path)
    rb = tuning.tune((32,) * 3, axis_sizes=SIZES, mode="model", batch=8,
                     wisdom_path=path)
    assert rb.key == r1.key + "|b8"
    # both keys recorded independently
    w = tuning.Wisdom.load(path)
    assert w.lookup(r1.key) is not None and w.lookup(rb.key) is not None


# --- pairwise-transpose rejection (satellite) --------------------------------

def test_pairwise_rejected_for_folded_and_cell():
    folded = Decomposition("pencil", (("a", "b"), "c"))
    sizes = {"a": 2, "b": 2, "c": 2}
    folded.validate((32,) * 3, sizes)  # fine with the fused all_to_all
    with pytest.raises(ValueError, match="pairwise"):
        folded.validate((32,) * 3, sizes, 1, "pairwise")
    with pytest.raises(ValueError, match="folded"):
        CELL.validate((32,) * 3, sizes, 1, "pairwise")
    assert not CELL.is_valid((32,) * 3, sizes, 1, "pairwise")
    # single-axis slab/pencil stay valid with pairwise
    SLAB.validate((32,) * 3, {"p": 8}, 1, "pairwise")
    # candidate generation never emits pairwise for cell meshes
    cands = tuning.enumerate_candidates((32,) * 3, sizes,
                                        include_baselines=True)
    for c in cands:
        if c.opts.transpose_impl == "pairwise":
            assert c.decomp.kind != "cell"
            assert all(not isinstance(a, tuple) for a in c.decomp.axes)


# --- transpose impls: alltoall / ring / pairwise -----------------------------

def test_transpose_impls_bitwise_identical():
    """The three global-transpose impls (and both chunk emission modes)
    are pure data-movement variants: every (impl, K, mode) point must
    produce the *bitwise identical* transform — across pencil, slab and
    cell, c2c and packed r2c, including the K-chunked pipelined path
    (K=3's chunk-indivisible fallback is covered by
    ``test_chunk_fallback_matches_k1_numerics`` — pencil/slab validation
    rejects indivisible K at plan build)."""
    run_multidevice("""
import numpy as np, jax, jax.numpy as jnp
from repro.core import Croft3D, Decomposition, FFTOptions
N = 16
rng = np.random.RandomState(0)
xc = (rng.randn(N,N,N) + 1j*rng.randn(N,N,N)).astype(np.complex64)
xr = rng.randn(N,N,N).astype(np.float32)

def sweep(mesh, dec, impls, problem, xin, ref):
    outs = {}
    kw = dict(problem="r2c", strategy="packed") if problem == "r2c" else {}
    for impl in impls:
        for k in (1, 2, 4):
            for mode in ("pipelined", "unrolled"):
                plan = Croft3D((N,N,N), mesh, dec,
                               FFTOptions(overlap_k=k, transpose_impl=impl,
                                          overlap_mode=mode), **kw)
                xd = jax.device_put(jnp.asarray(xin), plan.input_sharding)
                outs[(impl, k, mode)] = np.asarray(plan.forward(xd))
    base = outs[(impls[0], 1, "pipelined")]
    err = np.max(np.abs(base - ref)) / np.abs(ref).max()
    assert err < 1e-5, err
    for key, v in outs.items():
        assert np.array_equal(v, base), f"transform differs at {key}"

ALL = ("alltoall", "ring", "pairwise")
mesh2 = jax.make_mesh((2,4), ("y","z"),
                      axis_types=(jax.sharding.AxisType.Auto,)*2)
pencil = Decomposition("pencil", ("y","z"))
sweep(mesh2, pencil, ALL, "c2c", xc, np.fft.fftn(xc))
sweep(mesh2, pencil, ALL, "r2c", xr, np.fft.rfftn(xr))
mesh1 = jax.make_mesh((8,), ("p",),
                      axis_types=(jax.sharding.AxisType.Auto,))
slab = Decomposition("slab", ("p",))
sweep(mesh1, slab, ALL, "c2c", xc, np.fft.fftn(xc))
sweep(mesh1, slab, ALL, "r2c", xr, np.fft.rfftn(xr))
mesh3 = jax.make_mesh((2,2,2), ("a","b","c"),
                      axis_types=(jax.sharding.AxisType.Auto,)*3)
cell = Decomposition("cell", ("a","b","c"))
sweep(mesh3, cell, ("alltoall",), "c2c", xc, np.fft.fftn(xc))
# ring/pairwise over the cell's folded regroup communicator must be
# rejected at plan-build time, not fail inside shard_map
for impl in ("ring", "pairwise"):
    try:
        Croft3D((N,N,N), mesh3, cell, FFTOptions(transpose_impl=impl))
        raise AssertionError(f"cell + {impl} was not rejected")
    except ValueError:
        pass
print("OK transpose impls bitwise identical")
""", timeout=900)


def test_transpose_pack_kernels(rng):
    """rotate_blocks / pack_pieces / unpack_pieces on the executor's
    stacked planes: jnp fallback and the Pallas plane kernel agree with
    the roll reference, traced and concrete, never move the plane axis,
    and pack -> unpack round-trips the ring's permutation."""
    import jax
    import jax.numpy as jnp
    from repro.core.local_fft import to_planes
    from repro.kernels import transpose_pack as tp

    x = (rng.randn(4, 24, 5) + 1j * rng.randn(4, 24, 5)).astype(np.complex64)
    x = np.asarray(to_planes(jnp.asarray(x)))            # (2, 4, 24, 5)
    p = 8
    for shift in (0, 1, 3, -2, 11):
        ref = np.roll(x, -(shift % p) * 3, axis=2)
        got = np.asarray(tp.rotate_blocks(jnp.asarray(x), 2, shift, p,
                                          use_pallas=False))
        np.testing.assert_array_equal(got, ref)
        ker = np.asarray(tp.rotate_blocks(jnp.asarray(x), 2, shift, p,
                                          use_pallas=True, interpret=True))
        np.testing.assert_array_equal(ker, ref)
    # traced shift (what shard_map's axis_index produces)
    f = jax.jit(lambda a, s: tp.rotate_blocks(a, 2, s, p, use_pallas=False))
    got = np.asarray(f(jnp.asarray(x), jnp.asarray(2)))
    np.testing.assert_array_equal(got, np.roll(x, -6, axis=2))

    # pack: piece s is the block bound for rank (idx + s) % p
    for idx in (0, 2, 7):
        for use_pallas in (False, True):
            pieces = tp.pack_pieces(jnp.asarray(x), 2, idx, p,
                                    use_pallas=use_pallas)
            assert len(pieces) == p
            for s, piece in enumerate(pieces):
                d = (idx + s) % p
                np.testing.assert_array_equal(np.asarray(piece),
                                              x[:, :, d * 3:(d + 1) * 3])
            # unpack: result block i = pieces[(i + shift) % p]
            out = np.asarray(tp.unpack_pieces(pieces, 2, -idx,
                                              use_pallas=use_pallas))
            rot = np.asarray(tp.rotate_blocks(jnp.concatenate(pieces, 2), 2,
                                              -idx, p, use_pallas=False))
            np.testing.assert_array_equal(out, rot)

    with pytest.raises(ValueError):
        tp.rotate_blocks(jnp.asarray(x), 2, 1, 7)  # 24 % 7 != 0
    with pytest.raises(ValueError, match="planes"):
        tp.rotate_blocks(jnp.asarray(x), 0, 1, 2)


def test_fftoptions_overlap_knobs():
    o = FFTOptions(overlap_mode=("pipelined", "unrolled", "pipelined"),
                   transpose_impl="ring")
    assert o.stage_overlap(1) == "unrolled"
    assert o.stage_overlap(2) == "pipelined"
    # homogeneous tuples collapse (canonical wisdom-key form)
    assert FFTOptions(overlap_mode=("unrolled",) * 3).overlap_mode == "unrolled"
    with pytest.raises(ValueError, match="transpose_impl"):
        FFTOptions(transpose_impl="bruck")
    with pytest.raises(ValueError, match="overlap_mode"):
        FFTOptions(overlap_mode="eager")
    with pytest.raises(ValueError):
        FFTOptions(overlap_mode=("pipelined", "unrolled"))  # needs 3


def test_ring_rejected_for_folded_and_cell():
    folded = Decomposition("pencil", (("a", "b"), "c"))
    sizes = {"a": 2, "b": 2, "c": 2}
    with pytest.raises(ValueError, match="ring"):
        folded.validate((32,) * 3, sizes, 1, "ring")
    with pytest.raises(ValueError, match="folded"):
        CELL.validate((32,) * 3, sizes, 1, "ring")
    SLAB.validate((32,) * 3, {"p": 8}, 1, "ring")  # single axis: fine
    # the DEFAULT candidate space carries ring wherever it can trace —
    # and only there (no folded axes, no cell; on this 2-axis mesh that
    # is the single-axis pencil points)
    cands = tuning.enumerate_candidates((32,) * 3, SIZES)
    by_impl = {}
    for c in cands:
        by_impl.setdefault(c.opts.transpose_impl, []).append(c)
    assert "ring" in by_impl and "pairwise" not in by_impl
    for c in by_impl["ring"]:
        assert c.decomp.kind != "cell"
        assert all(not isinstance(a, tuple) for a in c.decomp.axes)


def test_cost_model_transpose_impl_split():
    """The alpha/beta split: ring pays K*(P-1) launches plus pack/unpack
    passes but overlaps its bandwidth term even at K=1; pairwise pays
    the same launches plus a serialized placement chain; alltoall keeps
    the legacy behaviour (one alpha per chunk, overlap only at K>=2).
    The ranking emerges from the terms — ring beats the unoverlapped
    alltoall once bytes dominate, and pairwise never wins."""
    sizes = SIZES
    mk = lambda impl, k=1: tuning.Candidate(PENCIL, FFTOptions(
        overlap_k=k, transpose_impl=impl, output_layout="spectral"))
    a1 = tuning.analytic_cost((128,) * 3, mk("alltoall"), sizes)
    r1 = tuning.analytic_cost((128,) * 3, mk("ring"), sizes)
    p1 = tuning.analytic_cost((128,) * 3, mk("pairwise"), sizes)
    # launch counts: 2 stages over (data=2, model=4) -> a2a 2, ring/pw
    # (2-1) + (4-1) = 4 ppermute rounds
    assert a1.n_collectives == 2
    assert r1.n_collectives == 4 and p1.n_collectives == 4
    assert r1.transpose_overhead_s > 0 and p1.transpose_overhead_s > 0
    assert a1.transpose_overhead_s == 0
    # at 128^3 the overlapped ring beats the unoverlapped alltoall and
    # the serialized pairwise loses to both — no hardcoded preference,
    # pure arithmetic (at 32^3 the alpha terms flip ring below alltoall)
    assert r1.total_s < a1.total_s
    assert p1.total_s > a1.total_s
    small_r = tuning.analytic_cost((32,) * 3, mk("ring"), sizes)
    small_a = tuning.analytic_cost((32,) * 3, mk("alltoall"), sizes)
    assert small_r.total_s > small_a.total_s
    # ring launches scale with K; model ranks via the same terms
    r4 = tuning.analytic_cost((128,) * 3, mk("ring", 4), sizes)
    assert r4.n_collectives == 4 * 4
    # mode="model" ranks the ring candidates alongside everything else
    res = tuning.tune((128,) * 3, axis_sizes=sizes, mode="model")
    labels = [row["label"] for row in res.ranked]
    assert any("/ring" in l for l in labels)


def test_ring_moves_fewer_collective_bytes_than_alltoall():
    """Compiled 64^3 forwards on the 2x4 pencil at K=1: the ring's
    (2-1) + (4-1) collective-permute rounds move strictly fewer bytes
    than the fused all-to-alls, because a rank's own piece never
    crosses the wire."""
    run_multidevice("""
from repro.core import Croft3D, Decomposition, FFTOptions
from repro.launch.mesh import make_mesh
from repro.tuning import cost_model
mesh = make_mesh((2, 4), ("y", "z"))
dec = Decomposition("pencil", ("y", "z"))
hlo = {impl: cost_model.hlo_collectives(Croft3D(
           (64, 64, 64), mesh, dec,
           FFTOptions(overlap_k=1, transpose_impl=impl,
                      output_layout="spectral")))
       for impl in ("alltoall", "ring")}
ring, a2a = hlo["ring"], hlo["alltoall"]
permutes = sum(v["count"] for k, v in ring["collectives"].items()
               if "permute" in k)
assert permutes == (2 - 1) + (4 - 1), ring["collectives"]
assert ring["collective_bytes"] < a2a["collective_bytes"], (
    ring["collective_bytes"], a2a["collective_bytes"])
print("OK")
""")


# --- fused epilogue ----------------------------------------------------------

def test_with_epilogue_structure():
    sched = build_schedule(PENCIL, FFTOptions(output_layout="spectral"))
    fused = sched.with_epilogue(schedule_lib.SpectralScale())
    assert len(fused.epilogue) == 1
    assert "kscale[filter]" in fused.describe()
    assert fused.layout_out == sched.layout_out  # pointwise: layout kept
    # executor demands the operand
    with pytest.raises(schedule_lib.ScheduleError, match="filter"):
        schedule_lib.SpectralScale().apply(jnp.ones((2, 2, 2),
                                                    jnp.complex64),
                                           FFTOptions(), {}, 0)


@pytest.mark.parametrize("use_pallas", [False, True])
def test_spectral_scale_on_planes_matches_reference(use_pallas, rng):
    """The schedule epilogue's planes form: same-shape filters (the
    Pallas plane kernel, interpreted here, or jnp) and a filter
    broadcast over a leading batch axis (jnp)."""
    from repro.core.local_fft import from_planes, to_planes
    from repro.kernels.spectral_scale import spectral_scale_stacked
    x = (rng.randn(2, 4, 4, 8) + 1j * rng.randn(2, 4, 4, 8)).astype(
        np.complex64)
    h = (rng.randn(2, 4, 4, 8) + 1j * rng.randn(2, 4, 4, 8)).astype(
        np.complex64)
    for hh in (h, h[0]):
        got = from_planes(spectral_scale_stacked(
            to_planes(jnp.asarray(x)), jnp.asarray(hh), 0.5,
            use_pallas=use_pallas, interpret=True))
        np.testing.assert_allclose(np.asarray(got), 0.5 * x * hh, atol=1e-6)


def test_spectral_scale_helper_matches_reference(rng):
    from repro.kernels.spectral_scale import spectral_scale
    x = (rng.randn(4, 4, 8) + 1j * rng.randn(4, 4, 8)).astype(np.complex64)
    h = (rng.randn(4, 4, 8) + 1j * rng.randn(4, 4, 8)).astype(np.complex64)
    ref = 0.5 * x * h
    got = np.asarray(spectral_scale(jnp.asarray(x), jnp.asarray(h), 0.5,
                                    use_pallas=False))
    np.testing.assert_allclose(got, ref, atol=1e-6)
    ker = np.asarray(spectral_scale(jnp.asarray(x), jnp.asarray(h), 0.5,
                                    use_pallas=True, interpret=True))
    np.testing.assert_allclose(ker, ref, atol=1e-6)


# --- adjoint schedules (repro.grad) ------------------------------------------

def test_adjoint_mirrors_layouts_and_comm_volume():
    """The adjoint runs output-layout -> input-layout with the same
    transpose count and the same total moved bytes — the symbolic
    foundation under the ``_grad`` cost model and the backward-HLO
    mirror gate in ``test_grad.py``."""
    shape = (32, 32, 32)
    cases = [
        (build_schedule(PENCIL, FFTOptions()), SIZES),
        (build_schedule(PENCIL, FFTOptions(output_layout="spectral")),
         SIZES),
        (build_schedule(SLAB, FFTOptions()), {"p": 8}),
        (build_schedule(CELL, FFTOptions()), {"a": 2, "b": 2, "c": 2}),
        (build_packed_forward(PENCIL), SIZES),
        (build_packed_forward(SLAB), {"p": 8}),
    ]
    for sched, sizes in cases:
        adj = adjoint_schedule(sched)
        assert (adj.layout_in.partition_spec()
                == sched.layout_out.partition_spec()), sched.name
        assert (adj.layout_out.partition_spec()
                == sched.layout_in.partition_spec()), sched.name
        assert adj.transpose_count() == sched.transpose_count(), sched.name
        fwd_bytes = sum(ev["bytes"] for ev in sched.comm_events(shape, sizes))
        adj_bytes = sum(ev["bytes"] for ev in adj.comm_events(shape, sizes))
        assert adj_bytes == fwd_bytes, sched.name


def test_cost_model_grad_prices_forward_plus_adjoint():
    """``c2c_grad`` is modeled as the forward schedule plus its adjoint:
    exactly double every volume/launch term when the adjoint is an exact
    mirror (all c2c layouts), and strictly pricier-than-forward for the
    packed r2c pipeline (mirrored comm, halved-volume compute)."""
    shape = (64,) * 3
    for opts in (FFTOptions(), FFTOptions(output_layout="spectral")):
        b = tuning.analytic_cost(shape, tuning.Candidate(PENCIL, opts), SIZES)
        g = tuning.analytic_cost(
            shape, tuning.Candidate(PENCIL, opts, problem="c2c_grad"), SIZES)
        assert g.flops == 2 * b.flops
        assert g.collective_bytes == 2 * b.collective_bytes
        assert g.n_collectives == 2 * b.n_collectives
        assert g.total_s == pytest.approx(2 * b.total_s)
    spec = FFTOptions(output_layout="spectral")
    rb = tuning.analytic_cost(shape, tuning.Candidate(
        PENCIL, spec, problem="r2c", strategy="packed"), SIZES)
    rg = tuning.analytic_cost(shape, tuning.Candidate(
        PENCIL, spec, problem="r2c_grad", strategy="packed"), SIZES)
    assert rb.total_s < rg.total_s <= 2.5 * rb.total_s
    assert rg.collective_bytes == 2 * rb.collective_bytes


def test_per_stage_costs_grad_directions_and_launch_prediction():
    """``per_stage_costs`` rows for a ``_grad`` candidate split into fwd
    and bwd directions, and the bwd all-to-all launch prediction (one per
    effective-K chunk) mirrors the forward exactly — this is the number
    the training bench gates the compiled backward HLO against."""
    cand = tuning.Candidate(
        PENCIL, FFTOptions(output_layout="spectral", overlap_k=2),
        problem="c2c_grad")
    rows = tuning.per_stage_costs((32,) * 3, cand, SIZES)
    fwd = [r for r in rows if r["direction"] == "fwd"]
    bwd = [r for r in rows if r["direction"] == "bwd"]
    assert fwd and bwd and len(fwd) + len(bwd) == len(rows)
    launches = lambda rs: sum(int(r["k_eff"]) for r in rs
                              if r["collective_s"] > 0)
    # 2 transposes x K=2 chunks each way
    assert launches(fwd) == launches(bwd) == 4
    # non-grad candidates stay single-direction (back-compat)
    base = tuning.per_stage_costs(
        (32,) * 3, tuning.Candidate(PENCIL, FFTOptions()), SIZES)
    assert {r["direction"] for r in base} == {"fwd"}


def test_wisdom_key_grad_dimension():
    """``|grad`` is a key dimension like batch: appended last, after the
    problem and ``|b{B}`` slots, so forward wisdom never aliases a
    training-step entry and legacy keys are untouched."""
    base = tuning.wisdom_key((32,) * 3, SIZES, jnp.complex64, "cpu")
    kg = tuning.wisdom_key((32,) * 3, SIZES, jnp.complex64, "cpu",
                           "c2c_grad")
    assert kg == base + "|grad"
    kr = tuning.wisdom_key((32,) * 3, SIZES, jnp.complex64, "cpu",
                           "r2c_grad", 4)
    assert kr.endswith("|r2c|b4|grad")
    assert tuning.wisdom_key((32,) * 3, SIZES, jnp.complex64, "cpu",
                             "r2c", 4) == kr[: -len("|grad")]


def test_ring_adjoint_collective_permute_rounds():
    """Ring-transpose pullback: the compiled backward issues exactly the
    forward's collective-permute count — K*(P_axis-1) rounds summed over
    stages — i.e. the custom VJP replays the ring schedule rather than
    letting XLA invent a different (or impossible) transpose."""
    run_multidevice("""
import jax, jax.numpy as jnp
from repro.core import Croft3D, Decomposition, FFTOptions
from repro.launch import hlo_cost
mesh = jax.make_mesh((2,4), ("data","model"),
                     axis_types=(jax.sharding.AxisType.Auto,)*2)
dec = Decomposition("pencil", ("data","model"))
N, K = 16, 2
plan = Croft3D((N,N,N), mesh, dec,
               FFTOptions(output_layout="spectral", transpose_impl="ring",
                          overlap_k=K))
x = jax.device_put(jnp.zeros((N,N,N), jnp.complex64), plan.input_sharding)

def counts(fn, *args):
    txt = jax.jit(fn).lower(*args).compile().as_text()
    return {k: int(v["count"])
            for k, v in hlo_cost.analyze(txt).collectives.items()}

fwd = counts(plan._fwd, x)
y, pull = jax.vjp(plan._fwd, x)
bwd = counts(pull, jnp.ones_like(y))
# spectral pencil: one ring stage over data (P=2), one over model (P=4)
expect = K * (2 - 1) + K * (4 - 1)
assert fwd.get("collective-permute", 0) == expect, fwd
assert bwd == fwd, (fwd, bwd)
print("OK ring adjoint rounds", expect)
""", timeout=900)
