"""``custom_vjp`` wiring: plan-reusing backward passes for every entry point.

Without this module, ``jax.grad`` through the distributed transform would
differentiate the ``shard_map`` body op by op — impossible for the
pairwise transpose (``optimization_barrier`` has no differentiation
rule) and plan-oblivious everywhere else.  Here each entry point gets a
``jax.custom_vjp`` whose backward pass runs the *adjoint schedule*
(:func:`repro.grad.adjoint.adjoint_schedule`) under the same executor,
options, overlap engine and transpose impl as the forward — so the
backward HLO has exactly the forward schedule's collective structure,
and the tuner can price a training step as forward + adjoint.

Scaling: norm factors are real scalars, so the transpose of
``x -> scale * F x`` is ``ct -> scale * F^T ct`` — the same ``scale``
rides both directions.  All linear paths are residual-free (the vjp
closes over the plan, not activations); only the filtered transform
stores one spectrum, needed for the filter's own gradient.

Everything is cached per ``(mesh, schedule, opts, scale, nbatch)`` so
repeated calls (``Croft3D``'s jitted entry points, the tuner's
measurement loop) reuse one ``custom_vjp`` instance per plan.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map
from repro.core import schedule as schedule_lib
from repro.obs import scopes
from repro.grad.adjoint import (adjoint_schedule, fold_dc_plane_t,
                                unfold_dc_plane_t)


def _with_batch(spec, n: int):
    if n == 0:
        return spec
    return P(*((None,) * n), *spec)


@scopes.role(scopes.SCALE)
def _scaled(y: jax.Array, scale) -> jax.Array:
    return y if scale is None else y * jnp.asarray(scale, y.dtype)


def _runner(mesh, sched, opts, scale, in_spec, out_spec, operands=None):
    """shard_map(run_schedule) with the scalar norm folded in-body."""
    def body(blk, *ops_blocks):
        ctx = dict(zip(operands or (), ops_blocks))
        out = schedule_lib.run_schedule(blk, sched, opts, operands=ctx)
        return _scaled(out, scale)
    return shard_map(body, mesh=mesh, in_specs=in_spec, out_specs=out_spec)


# ---------------------------------------------------------------------------
# complex transform (distributed_fft3d's body): y = scale * F x
# ---------------------------------------------------------------------------

class LinearPlan:
    """A schedule + its adjoint as a ``custom_vjp``-wrapped callable.

    ``apply`` is the forward (identical ops to the pre-grad path, so
    primal results and HLO are unchanged); its vjp runs ``adjoint`` —
    the transposed schedule under the same options.  ``adjoint`` is also
    exposed raw for composition (the filtered transform, the tuner's
    backward-only timings).
    """

    def __init__(self, mesh: Mesh, sched: schedule_lib.Schedule, opts,
                 scale, nbatch: int):
        self.schedule = sched
        self.adjoint_schedule = adjoint_schedule(sched)
        in_spec = _with_batch(sched.layout_in.partition_spec(), nbatch)
        out_spec = _with_batch(sched.layout_out.partition_spec(), nbatch)

        def fwd(x):
            return _runner(mesh, sched, opts, scale, in_spec, out_spec)(x)

        def adj(ct):
            return _runner(mesh, self.adjoint_schedule, opts, scale,
                           out_spec, in_spec)(ct)

        f = jax.custom_vjp(fwd)
        f.defvjp(lambda x: (fwd(x), None), lambda _, ct: (adj(ct),))
        self.apply = f
        self.adjoint = adj


@functools.lru_cache(maxsize=512)
def linear_plan(mesh: Mesh, sched: schedule_lib.Schedule, opts, scale,
                nbatch: int = 0) -> LinearPlan:
    return LinearPlan(mesh, sched, opts, scale, nbatch)


@functools.lru_cache(maxsize=512)
def filtered_plan(mesh: Mesh, sched: schedule_lib.Schedule, opts, scale,
                  nbatch: int = 0):
    """``(x, h) -> scale * (h * F x)`` differentiable in both arguments.

    The primal keeps the fused in-schedule epilogue (``SpectralScale``
    as a terminal schedule op — no extra pass over the spectrum when not
    differentiating).  Under differentiation the forward runs unfused so
    the pre-filter spectrum ``s`` can be saved: the cotangent of ``x``
    is the adjoint schedule applied to ``h * ct`` (the k-space multiply
    is its own transpose under JAX's unconjugated ``mul`` rule), and the
    cotangent of ``h`` is ``s * ct``.
    """
    lin = linear_plan(mesh, sched, opts, scale, nbatch)
    fused = sched.with_epilogue(schedule_lib.SpectralScale())
    in_spec = _with_batch(sched.layout_in.partition_spec(), nbatch)
    out_spec = _with_batch(sched.layout_out.partition_spec(), nbatch)

    def primal(x, h):
        return _runner(mesh, fused, opts, scale, (in_spec, out_spec),
                       out_spec, operands=("filter",))(x, h)

    def fwd(x, h):
        from repro.kernels import spectral_scale as ss
        s = lin.apply(x)
        return ss.spectral_scale(s, h), (s, h)

    def bwd(res, ct):
        from repro.kernels import spectral_scale as ss
        s, h = res
        return lin.adjoint(ss.spectral_scale(ct, h)), ss.spectral_scale(ct, s)

    f = jax.custom_vjp(primal)
    f.defvjp(fwd, bwd)
    return f


# ---------------------------------------------------------------------------
# packed real transforms (the r2c/c2r pipelines of repro.real.pipeline)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=512)
def packed_rfft_plan(mesh: Mesh, decomp, opts, scale, nbatch: int = 0):
    """Linear core of ``packed_rfft3d``: real x -> rfftn-style spectrum.

    Forward: packed body -> z-localizing reshard -> DC/Nyquist plane
    unfold -> norm scale.  Backward (the transpose, right to left):
    scale -> plane-unfold transpose -> reshard -> adjoint body, ending in
    the transposed pack (a real cotangent, matching the real input).
    """
    from repro.real import pipeline
    sched = pipeline.build_packed_forward(decomp)
    adj = adjoint_schedule(sched)
    in_spec = _with_batch(sched.layout_in.partition_spec(), nbatch)
    body_spec = _with_batch(sched.layout_out.partition_spec(), nbatch)
    spect_sh = NamedSharding(mesh, _with_batch(decomp.spectral_spec(), nbatch))

    def fwd(x):
        packed = pipeline.constrain_sharding(
            _runner(mesh, sched, opts, None, in_spec, body_spec)(x), spect_sh)
        y = pipeline.constrain_sharding(
            pipeline.unfold_dc_plane(packed), spect_sh)
        return _scaled(y, scale)

    def adj_fn(ct):
        ctp = unfold_dc_plane_t(
            pipeline.constrain_sharding(_scaled(ct, scale), spect_sh))
        return _runner(mesh, adj, opts, None, body_spec, in_spec)(ctp)

    f = jax.custom_vjp(fwd)
    f.defvjp(lambda x: (fwd(x), None), lambda _, ct: (adj_fn(ct),))
    return f


@functools.lru_cache(maxsize=512)
def packed_rfft_folded_plan(mesh: Mesh, decomp, opts, scale, nbatch: int = 0,
                            h_nbatch: int = 0):
    """Folded-epilogue variant: ``(x, h_packed) -> scale * unfold(h_p * body(x))``.

    The filter rides the packed half spectrum *before* the plane unfold
    (one fused in-schedule multiply on Nz/2 bins instead of a separate
    pass over Nz/2 + 1), valid when ``h(kz=0) == h(kz=Nyquist)`` and
    that plane is 2-D Hermitian.  The gradient is the gradient of this
    implemented map: ``h_packed``'s cotangent is ``body(x) * unfoldT(ct)``
    (the primal never reads the filter's Nyquist plane).
    """
    from repro.real import pipeline
    sched = pipeline.build_packed_forward(decomp)
    adj = adjoint_schedule(sched)
    fused = sched.with_epilogue(schedule_lib.SpectralScale())
    in_spec = _with_batch(sched.layout_in.partition_spec(), nbatch)
    body_spec = _with_batch(sched.layout_out.partition_spec(), nbatch)
    h_spec = _with_batch(sched.layout_out.partition_spec(), h_nbatch)
    spect_sh = NamedSharding(mesh, _with_batch(decomp.spectral_spec(), nbatch))

    def primal(x, hp):
        bf = pipeline.constrain_sharding(
            _runner(mesh, fused, opts, None, (in_spec, h_spec), body_spec,
                    operands=("filter",))(x, hp), spect_sh)
        return _scaled(pipeline.unfold_dc_plane(bf), scale)

    def fwd(x, hp):
        b = pipeline.constrain_sharding(
            _runner(mesh, sched, opts, None, in_spec, body_spec)(x), spect_sh)
        from repro.kernels import spectral_scale as ss
        y = _scaled(pipeline.unfold_dc_plane(ss.spectral_scale(b, hp)), scale)
        return y, (b, hp)

    def bwd(res, ct):
        from repro.kernels import spectral_scale as ss
        b, hp = res
        ctu = unfold_dc_plane_t(
            pipeline.constrain_sharding(_scaled(ct, scale), spect_sh))
        xb = _runner(mesh, adj, opts, None, body_spec, in_spec)(
            ss.spectral_scale(ctu, hp))
        hb = ss.spectral_scale(ctu, b)
        if h_nbatch < nbatch:  # unbatched filter over a batched field
            hb = hb.sum(axis=tuple(range(nbatch - h_nbatch)))
        return xb, hb

    f = jax.custom_vjp(primal)
    f.defvjp(fwd, bwd)
    return f


@functools.lru_cache(maxsize=512)
def packed_irfft_plan(mesh: Mesh, decomp, nz: int, opts, scale,
                      nbatch: int = 0):
    """Linear core of ``packed_irfft3d``: rfftn-style spectrum -> real x.

    Forward: DC/Nyquist plane fold -> packed inverse body -> norm scale.
    Backward: scale -> adjoint body -> plane-fold transpose.
    """
    from repro.real import pipeline
    sched = pipeline.build_packed_inverse(decomp, nz)
    adj = adjoint_schedule(sched)
    in_spec = _with_batch(sched.layout_in.partition_spec(), nbatch)
    out_spec = _with_batch(sched.layout_out.partition_spec(), nbatch)
    spect_sh = NamedSharding(mesh, _with_batch(decomp.spectral_spec(), nbatch))

    def fwd(y):
        packed = pipeline.fold_dc_plane(
            pipeline.constrain_sharding(y, spect_sh), nz)
        return _scaled(_runner(mesh, sched, opts, None, in_spec,
                               out_spec)(packed), scale)

    def adj_fn(ct):
        pbar = pipeline.constrain_sharding(
            _runner(mesh, adj, opts, None, out_spec, in_spec)(
                _scaled(ct, scale)), spect_sh)
        return pipeline.constrain_sharding(fold_dc_plane_t(pbar, nz), spect_sh)

    f = jax.custom_vjp(fwd)
    f.defvjp(lambda y: (fwd(y), None), lambda _, ct: (adj_fn(ct),))
    return f
