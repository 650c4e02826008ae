"""repro.grad correctness: adjoint-schedule gradients vs autodiff oracles.

The single-device entry points differentiate directly against ``jnp.fft``
autodiff (norm modes included).  The distributed matrix — problem x batch
x all three transpose impls — runs on 8 virtual devices and pins every
impl's gradient to the alltoall plan's gradient: the impls are
bitwise-identical forward, so their VJPs must agree to float tolerance,
even though the pairwise path is not XLA-differentiable at all (the
plan-level custom VJP is the only route through its
``optimization_barrier`` chain).  The folded-epilogue test is the formal
gate for the fused k-space multiply's adjoint placement.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from conftest import multidevice_report, run_multidevice
from repro.core import fft3d, ifft3d, irfft3d, rfft3d


def _rel(a, b):
    den = max(float(jnp.max(jnp.abs(b))), 1e-30)
    return float(jnp.max(jnp.abs(a - b))) / den


# --- single-device oracles ---------------------------------------------------

@pytest.mark.parametrize("norm", [None, "ortho"])
def test_local_c2c_grads_match_jnp(rng, norm):
    n = 8
    x = jnp.asarray((rng.randn(n, n, n)
                     + 1j * rng.randn(n, n, n)).astype(np.complex64))
    ct = jnp.asarray((rng.randn(n, n, n)
                      + 1j * rng.randn(n, n, n)).astype(np.complex64))
    _, pull = jax.vjp(lambda v: fft3d(v, norm=norm), x)
    _, ref = jax.vjp(lambda v: jnp.fft.fftn(v, norm=norm), x)
    assert _rel(pull(ct)[0], ref(ct)[0]) < 1e-5
    inorm = norm or "backward"
    _, ipull = jax.vjp(lambda v: ifft3d(v, norm=inorm), x)
    _, iref = jax.vjp(lambda v: jnp.fft.ifftn(v, norm=inorm), x)
    assert _rel(ipull(ct)[0], iref(ct)[0]) < 1e-5


@pytest.mark.parametrize("norm", [None, "ortho"])
def test_local_r2c_grads_match_jnp(rng, norm):
    n = 8
    x = jnp.asarray(rng.randn(n, n, n).astype(np.float32))
    ct = jnp.asarray((rng.randn(n, n, n // 2 + 1)
                      + 1j * rng.randn(n, n, n // 2 + 1))
                     .astype(np.complex64))
    _, pull = jax.vjp(lambda v: rfft3d(v, norm=norm), x)
    _, ref = jax.vjp(lambda v: jnp.fft.rfftn(v, norm=norm), x)
    assert _rel(pull(ct)[0], ref(ct)[0]) < 1e-5
    y = jnp.fft.rfftn(x, norm=norm)
    ctr = jnp.asarray(rng.randn(n, n, n).astype(np.float32))
    _, ipull = jax.vjp(lambda v: irfft3d(v, n, norm=norm), y)
    _, iref = jax.vjp(lambda v: jnp.fft.irfftn(v, (n, n, n), norm=norm), y)
    assert _rel(ipull(ctr)[0], iref(ctr)[0]) < 1e-5


# --- distributed matrix: problem x batch x transpose impl --------------------

def test_distributed_grad_matrix():
    """Every transpose impl's plan-level gradient equals the alltoall
    plan's, c2c and packed r2c, single and vmapped-batch — the adjoint
    schedule is impl-agnostic data movement, so the grads must be too."""
    run_multidevice("""
import numpy as np, jax, jax.numpy as jnp
from repro.core import Croft3D, Decomposition, FFTOptions

N = 16
mesh = jax.make_mesh((2,4), ("data","model"),
                     axis_types=(jax.sharding.AxisType.Auto,)*2)
dec = Decomposition("pencil", ("data","model"))
rng = np.random.RandomState(0)
x1 = (rng.randn(N,N,N) + 1j*rng.randn(N,N,N)).astype(np.complex64)
xb = (rng.randn(2,N,N,N) + 1j*rng.randn(2,N,N,N)).astype(np.complex64)

def rel(a, b):
    return (float(jnp.max(jnp.abs(a - b)))
            / max(float(jnp.max(jnp.abs(b))), 1e-30))

for problem, kw in (("c2c", {}),
                    ("r2c", {"problem": "r2c", "strategy": "packed"})):
    for batch, x in ((1, x1), (2, xb)):
        grads = {}
        for impl in ("alltoall", "ring", "pairwise"):
            plan = Croft3D((N,N,N), mesh, dec,
                           FFTOptions(output_layout="spectral",
                                      transpose_impl=impl), **kw)
            xin = jnp.asarray(np.real(x) if problem == "r2c" else x,
                              plan.input_dtype)
            # pairwise has no batching rule (optimization_barrier), so
            # batch it unrolled — which also pins vmap batching of the
            # custom VJP against the unrolled reference
            if batch == 1:
                fwd = plan.forward
            elif impl == "pairwise":
                fwd = lambda v, f=plan.forward: jnp.stack(
                    [f(v[b]) for b in range(v.shape[0])])
            else:
                fwd = jax.vmap(plan.forward)
            def loss(v, fwd=fwd):
                y = fwd(v)
                return jnp.sum(jnp.real(y * jnp.conj(y)))
            grads[impl] = jax.jit(jax.grad(loss))(xin)
        for impl in ("ring", "pairwise"):
            r = rel(grads[impl], grads["alltoall"])
            assert r < 1e-4, (problem, batch, impl, r)
        print("OK", problem, "batch", batch)
print("OK distributed grad matrix")
""", timeout=900)


def test_distributed_norm_mode_grads():
    """Distributed functional entry points: VJPs match the jnp.fft oracle
    under both normalization conventions."""
    run_multidevice("""
import numpy as np, jax, jax.numpy as jnp
from repro.core import Decomposition, FFTOptions, fft3d, rfft3d

N = 16
mesh = jax.make_mesh((2,4), ("data","model"),
                     axis_types=(jax.sharding.AxisType.Auto,)*2)
dec = Decomposition("pencil", ("data","model"))
opts = FFTOptions(output_layout="spectral")
rng = np.random.RandomState(1)
x = jnp.asarray((rng.randn(N,N,N) + 1j*rng.randn(N,N,N))
                .astype(np.complex64))
ct = jnp.asarray((rng.randn(N,N,N) + 1j*rng.randn(N,N,N))
                 .astype(np.complex64))

def rel(a, b):
    return (float(jnp.max(jnp.abs(a - b)))
            / max(float(jnp.max(jnp.abs(b))), 1e-30))

for norm in (None, "ortho"):
    _, pull = jax.vjp(lambda v: fft3d(v, mesh, dec, opts, norm=norm), x)
    _, ref = jax.vjp(lambda v: jnp.fft.fftn(v, norm=norm), x)
    r = rel(pull(ct)[0], ref(ct)[0])
    assert r < 1e-4, ("c2c", norm, r)
    xr = jnp.real(x)
    ctr = ct[..., : N // 2 + 1]
    _, rpull = jax.vjp(lambda v: rfft3d(v, mesh, dec, opts,
                                        strategy="packed", norm=norm), xr)
    _, rref = jax.vjp(lambda v: jnp.fft.rfftn(v, norm=norm), xr)
    r = rel(rpull(ctr)[0], rref(ctr)[0])
    assert r < 1e-4, ("r2c", norm, r)
    print("OK norm", norm)
print("OK distributed norm grads")
""", timeout=900)


# --- folded spectral epilogue (satellite: fused-filter adjoint) --------------

def test_folded_filter_forward_and_grads_match_unfolded():
    """fold=True moves the k-space multiply before the DC/Nyquist unfold.
    For a compliant filter (kz-independent here: h(kz=0) == h(Nyquist)
    trivially, plane real and 2-D-even) the folded and unfolded
    pipelines are the same function of (x, g) — so outputs AND both
    gradients must agree, pinning the folded multiply's adjoint
    placement inside the packed schedule."""
    run_multidevice("""
import numpy as np, jax, jax.numpy as jnp
from repro.core import Croft3D, Decomposition, FFTOptions

N = 16
mesh = jax.make_mesh((2,4), ("data","model"),
                     axis_types=(jax.sharding.AxisType.Auto,)*2)
dec = Decomposition("pencil", ("data","model"))
plan = Croft3D((N,N,N), mesh, dec, FFTOptions(), problem="r2c",
               strategy="packed")
rng = np.random.RandomState(0)
x = jax.device_put(jnp.asarray(rng.randn(N,N,N), plan.input_dtype),
                   plan.input_sharding)
# 2-D-even real plane, tiled along kz: compliant for the folded path
neg = jnp.asarray((-np.arange(N)) % N)
g0 = rng.randn(N, N).astype(np.float32)
gj = jnp.asarray(0.5 * (g0 + g0[np.asarray(neg)][:, np.asarray(neg)]))

def loss(g, fold):
    # project onto the compliant manifold INSIDE the differentiated
    # function: fold==unfold only holds for compliant filters, so the
    # gradient comparison is only meaningful along compliant tangents
    ge = 0.5 * (g + g[neg][:, neg])
    h = jnp.broadcast_to(ge[:, :, None], plan.spectrum_shape)
    y = plan.forward_filtered(x, h, fold=fold)
    return jnp.sum(jnp.real(y * jnp.conj(y)))

h = jnp.broadcast_to(gj[:, :, None], plan.spectrum_shape)  # gj already even
y0 = plan.forward_filtered(x, h, fold=False)
y1 = plan.forward_filtered(x, h, fold=True)
rel_y = (float(jnp.max(jnp.abs(y1 - y0)))
         / float(jnp.max(jnp.abs(y0))))
assert rel_y < 1e-5, rel_y

l0, d0 = jax.value_and_grad(lambda g: loss(g, False))(gj)
l1, d1 = jax.value_and_grad(lambda g: loss(g, True))(gj)
assert abs(float(l1) - float(l0)) / abs(float(l0)) < 1e-5
rel_g = (float(jnp.max(jnp.abs(d1 - d0)))
         / max(float(jnp.max(jnp.abs(d0))), 1e-30))
assert rel_g < 1e-4, rel_g

# gradient w.r.t. the field agrees too (same linear operator both ways)
gx0 = jax.grad(lambda v: jnp.sum(jnp.real(
    (w := plan.forward_filtered(v, h, fold=False)) * jnp.conj(w))))(x)
gx1 = jax.grad(lambda v: jnp.sum(jnp.real(
    (w := plan.forward_filtered(v, h, fold=True)) * jnp.conj(w))))(x)
rel_x = (float(jnp.max(jnp.abs(gx1 - gx0)))
         / max(float(jnp.max(jnp.abs(gx0))), 1e-30))
assert rel_x < 1e-4, rel_x
print("OK folded filter fwd+grads", rel_y, rel_g, rel_x)
""", timeout=900)


# --- training through the transform (repro.grad.filter) ----------------------
#
# The learned spectral filter over a packed r2c plan on a 4x2 pencil,
# trained with seeded SGD, and its gradients held against two oracles
# that share no code with the adjoint schedules.

_FILTER_CODE = """
import json
import numpy as np, jax, jax.numpy as jnp
from repro.core import Croft3D, Decomposition, FFTOptions
from repro.grad.filter import (init_spectral_filter_params,
                               make_spectral_train_step,
                               place_spectral_filter_params,
                               spectral_filter_apply, spectral_loss_fn)
from repro.launch import hlo_cost
from repro.launch.mesh import make_mesh
from repro.tuning import Candidate, per_stage_costs

N, STEPS = 16, 10
shape = (N, N, N)
mesh = make_mesh((4, 2), ("y", "x"))
dec = Decomposition("pencil", ("y", "x"))
report = {}

def collective_counts(fn, *args):
    txt = jax.jit(fn).lower(*args).compile().as_text()
    return {k: int(v["count"])
            for k, v in hlo_cost.analyze(txt).collectives.items()}

plan = Croft3D(shape, mesh, dec, FFTOptions(), problem="r2c",
               strategy="packed")
rng = np.random.RandomState(0)
x = jax.device_put(jnp.asarray(rng.randn(*shape), plan.input_dtype),
                   plan.input_sharding)
true = place_spectral_filter_params(plan, {
    "gate": jnp.asarray(1.0 + 0.3 * rng.randn(*shape), jnp.float32),
    "filter": jnp.asarray(1.0 + 0.3 * rng.randn(*plan.spectrum_shape),
                          jnp.float32)})
target = spectral_filter_apply(plan, true, x)
step, loss_fn = make_spectral_train_step(plan, lr=0.05)
params = place_spectral_filter_params(
    plan, init_spectral_filter_params(jax.random.PRNGKey(1), plan))
losses = []
for _ in range(STEPS):
    params, loss = step(params, x, target)
    losses.append(float(loss))
report["losses"] = losses

# oracle 1: central finite differences; the loss is quadratic along any
# single-coordinate line, so they are exact up to float32 rounding
g = jax.jit(jax.grad(loss_fn))(params, x, target)
report["fd_rel"] = {}
for field in ("gate", "filter"):
    rel = 0.0
    for ij in [(1, 2, 3), (0, 0, 0), (3, 1, 2)]:
        def loss_at(v):
            pp = dict(params)
            pp[field] = params[field].at[ij].add(v)
            return float(loss_fn(pp, x, target))
        fd = (loss_at(0.5) - loss_at(-0.5)) / 1.0
        an = float(g[field][ij])
        rel = max(rel, abs(fd - an) / max(abs(fd), abs(an), 1e-6))
    report["fd_rel"][field] = rel

# oracle 2: the embed strategy, XLA autodiff over the Hermitian glue
embed = Croft3D(shape, mesh, dec, FFTOptions(), problem="r2c",
                strategy="embed")
ge = jax.jit(jax.grad(lambda p, v, t: spectral_loss_fn(embed, p, v, t)))(
    params, jax.device_put(x, embed.input_sharding), target)
report["embed_rel"] = {
    f: float(jnp.linalg.norm(g[f] - ge[f])) / float(jnp.linalg.norm(g[f]))
    for f in ("gate", "filter")}

# the c2c core's backward compiles to the forward's collectives, and its
# all-to-all count is the adjoint schedule's per-stage prediction
report["hlo"] = {}
for tag, opts in {
    "alltoall-natural": FFTOptions(),
    "alltoall-spectral": FFTOptions(output_layout="spectral"),
    "ring": FFTOptions(output_layout="spectral", transpose_impl="ring"),
    "pairwise": FFTOptions(output_layout="spectral",
                           transpose_impl="pairwise"),
}.items():
    cplan = Croft3D(shape, mesh, dec, opts)
    xc = jax.device_put(jnp.zeros(shape, jnp.complex64), cplan.input_sharding)
    y, pull = jax.vjp(cplan._fwd, xc)
    rec = {"fwd": collective_counts(cplan._fwd, xc),
           "bwd": collective_counts(pull, jnp.ones_like(y))}
    if opts.transpose_impl == "alltoall":
        rows = per_stage_costs(shape, Candidate(dec, opts, problem="c2c_grad"),
                               dict(mesh.shape))
        rec["predicted_bwd_all_to_all"] = sum(
            int(r["k_eff"]) for r in rows
            if r["direction"] == "bwd" and r["collective_s"] > 0)
    report["hlo"][tag] = rec
print("REPORT " + json.dumps(report))
"""


@pytest.fixture(scope="module")
def filter_training():
    return multidevice_report(_FILTER_CODE, timeout=900)


def test_filter_training_loss_decreases_and_halves(filter_training):
    losses = filter_training["losses"]
    assert all(b < a for a, b in zip(losses, losses[1:])), losses
    assert losses[-1] < 0.5 * losses[0], losses


@pytest.mark.parametrize("field", ["gate", "filter"])
def test_filter_grad_matches_finite_differences(filter_training, field):
    assert filter_training["fd_rel"][field] < 1e-2


@pytest.mark.parametrize("field", ["gate", "filter"])
def test_filter_packed_grad_matches_embed(filter_training, field):
    """Two gradient routes through different code: the packed plan's
    adjoint schedule and XLA autodiff over the embed strategy."""
    assert filter_training["embed_rel"][field] < 1e-4


@pytest.mark.parametrize("tag", ["alltoall-natural", "alltoall-spectral",
                                 "ring", "pairwise"])
def test_filter_backward_collectives_mirror_adjoint(filter_training, tag):
    rec = filter_training["hlo"][tag]
    assert rec["fwd"] and rec["bwd"] == rec["fwd"], rec
    if "predicted_bwd_all_to_all" in rec:
        assert rec["bwd"]["all-to-all"] == rec["predicted_bwd_all_to_all"]
