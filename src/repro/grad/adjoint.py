"""Adjoint schedules: the pure ``Schedule -> Schedule`` transpose.

The backward pass of a distributed FFT is the same scheduled machinery
run in reverse (P3DFFT phrases forward/inverse this way; ROADMAP item 1
names the olmax ``custom_gradient``-on-``all_to_all`` idiom).  Because
every pipeline is *data* (``repro.core.schedule``), the adjoint is a
mechanical walk over the stage list:

  * stage order reverses;
  * each global transpose swaps its split/concat axes (the transpose of
    a tiled ``all_to_all`` is the ``all_to_all`` that undoes it, over
    the same communicator, K-chunked along the same uninvolved axis);
  * each local FFT keeps its axis *and its sign*: JAX's linear-transpose
    convention does not conjugate, and the DFT matrix is symmetric, so
    the transpose of an unnormalized FFT with sign s is the unnormalized
    FFT with the same sign s (verified against ``jax.vjp(jnp.fft.fft)``);
  * each packed-real stage op maps to its explicit transpose (the folded
    two-for-one unpack weights DC/Nyquist bins differently from interior
    bins, so its transpose is *not* a scaled inverse — see the ``*T``
    ops below, each pinned against ``jax.vjp`` of its forward op);
  * terminal epilogue ops (the fused k-space multiply) transpose into
    leading prologue ops — ``x -> h * x`` is its own transpose under
    JAX's unconjugated ``mul`` rule.

The result is an ordinary :class:`~repro.core.schedule.Schedule`: the
existing symbolic layout propagation runs at construction, so a
malformed adjoint fails loudly at build time, and
:func:`adjoint_schedule` additionally checks that the propagated output
layout equals the forward input layout.  The cost model, the executor's
K-chunk overlap engine, and the golden ``describe()`` snapshots all work
on adjoints unchanged.

Out-of-body transposes of the packed pipeline's DC/Nyquist plane
fold/unfold (``real.pipeline.unfold_dc_plane`` / ``fold_dc_plane``) live
here too: they run at the traced global level, outside any schedule.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core.schedule import (_DIMS, PackTwo, RepackHalves, Schedule,
                                 ScheduleError, SpectralScale, SplitPairs,
                                 Stage, StageOp, UnpackTwo, complex_form)
from repro.obs import scopes
from repro.real import packing


# ---------------------------------------------------------------------------
# transposed packed-real stage ops.  Each ``FooT`` is the linear transpose
# of ``Foo`` under JAX's convention: T(complex(a,b))(ct) = (Re ct, -Im ct),
# T(real)(t) = complex(t, 0), T(imag)(t) = -i*t, T(conj) = conj,
# T(c * .) = c * . (unconjugated), T(permutation) = inverse permutation.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PackTwoT(StageOp):
    """Transpose of :class:`PackTwo`: complex cotangent -> real block,
    ``concat(Re ct, -Im ct)`` along the pair axis."""

    pair_axis: int

    @complex_form
    @scopes.role(scopes.RELAYOUT)
    def apply(self, blk, opts, ctx, off):
        ax = self.pair_axis + off
        return jnp.concatenate([jnp.real(blk), -jnp.imag(blk)], axis=ax)

    def transform(self, layout):
        if layout.real:
            raise ScheduleError("pack2T needs a complex cotangent")
        return dataclasses.replace(
            layout.with_den(self.pair_axis, div=2), real=True)

    def describe(self):
        return f"pack2T[{_DIMS[self.pair_axis]}]"


@dataclasses.dataclass(frozen=True)
class SplitPairsT(StageOp):
    """Transpose of :class:`SplitPairs`: real cotangent halves (u, v)
    along the pair axis -> ``complex(u, -v)``."""

    pair_axis: int

    @complex_form
    @scopes.role(scopes.RELAYOUT)
    def apply(self, blk, opts, ctx, off):
        ax = self.pair_axis + off
        m = blk.shape[ax]
        u = jax.lax.slice_in_dim(blk, 0, m // 2, axis=ax)
        v = jax.lax.slice_in_dim(blk, m // 2, m, axis=ax)
        return jax.lax.complex(u, -v)

    def transform(self, layout):
        if not layout.real:
            raise ScheduleError("split2T needs a real cotangent")
        return dataclasses.replace(
            layout.with_den(self.pair_axis, mul=2), real=False)

    def describe(self):
        return f"split2T[{_DIMS[self.pair_axis]}]"


@dataclasses.dataclass(frozen=True)
class UnpackTwoT(StageOp):
    """Transpose of the folded :class:`UnpackTwo`.

    The folded unpack routes (DC, Nyquist) through Re/Im extractions and
    interior bins through the 0.5-weighted Hermitian split, so its
    transpose reconstructs a full packed spectrum with per-bin rules
    (NOT a scaled repack): with the cotangent split into halves
    (a, b) of ``nz2`` bins each along the pair axis,

      Ct[0]     = complex( Re a[0], -Re b[0])
      Ct[nz2]   = complex(-Im a[0],  Im b[0])
      Ct[k]     = (a[k] - i b[k]) / 2                    k = 1..nz2-1
      Ct[n - k] = conj(a[k] + i b[k]) / 2                k = 1..nz2-1
    """

    pair_axis: int
    z_axis: int = 2
    impl_stage: int = 0

    @complex_form
    @scopes.role(scopes.RELAYOUT)
    def apply(self, blk, opts, ctx, off):
        ax = self.pair_axis + off
        m = blk.shape[ax]
        a = jax.lax.slice_in_dim(blk, 0, m // 2, axis=ax)
        b = jax.lax.slice_in_dim(blk, m // 2, m, axis=ax)
        a0, b0 = a[..., 0], b[..., 0]
        c0 = jax.lax.complex(jnp.real(a0), -jnp.real(b0))
        cn = jax.lax.complex(-jnp.imag(a0), jnp.imag(b0))
        ak, bk = a[..., 1:], b[..., 1:]
        body = 0.5 * (ak - 1j * bk)
        tail = jnp.flip(0.5 * jnp.conj(ak + 1j * bk), -1)
        return jnp.concatenate(
            [c0[..., None], body, cn[..., None], tail], axis=-1)

    def transform(self, layout):
        return layout.with_den(self.pair_axis, mul=2).with_den(
            self.z_axis, div=2)

    def describe(self):
        return f"unpack2T[{_DIMS[self.pair_axis]}]"


@dataclasses.dataclass(frozen=True)
class RepackHalvesT(StageOp):
    """Transpose of the folded :class:`RepackHalves`: full packed
    cotangent (n bins) -> folded halves (a, b), ``nz2 = n // 2`` each:

      a[0] = complex( Re Ct[0], -Re Ct[nz2])
      b[0] = complex(-Im Ct[0],  Im Ct[nz2])
      a[k] =     Ct[k] + conj(Ct[n - k])                 k = 1..nz2-1
      b[k] = i * (Ct[k] - conj(Ct[n - k]))               k = 1..nz2-1
    """

    pair_axis: int
    nz: int
    z_axis: int = 2
    impl_stage: int = 2

    @complex_form
    @scopes.role(scopes.RELAYOUT)
    def apply(self, blk, opts, ctx, off):
        ax = self.pair_axis + off
        n = blk.shape[-1]
        nz2 = n // 2
        c0, cn = blk[..., 0], blk[..., nz2]
        a0 = jax.lax.complex(jnp.real(c0), -jnp.real(cn))
        b0 = jax.lax.complex(-jnp.imag(c0), jnp.imag(cn))
        body = blk[..., 1:nz2]
        tail = jnp.conj(jnp.flip(blk[..., nz2 + 1:], -1))
        ak = body + tail
        bk = 1j * (body - tail)
        A = jnp.concatenate([a0[..., None], ak], axis=-1)
        B = jnp.concatenate([b0[..., None], bk], axis=-1)
        return jnp.concatenate([A, B], axis=ax)

    def transform(self, layout):
        return layout.with_den(self.pair_axis, div=2).with_den(
            self.z_axis, mul=2)

    def describe(self):
        return f"repack2T[{_DIMS[self.pair_axis]}]"


def adjoint_ops(op: StageOp) -> tuple:
    """The transpose of one stage op (a tuple, spliced in adjoint order)."""
    if isinstance(op, PackTwo):
        return (PackTwoT(op.pair_axis),)
    if isinstance(op, SplitPairs):
        return (SplitPairsT(op.pair_axis),)
    if isinstance(op, UnpackTwo):
        return (UnpackTwoT(op.pair_axis, op.z_axis, op.impl_stage),)
    if isinstance(op, RepackHalves):
        return (RepackHalvesT(op.pair_axis, op.nz, op.z_axis, op.impl_stage),)
    if isinstance(op, SpectralScale):
        return (op,)  # x -> alpha * h * x is its own transpose (no conj)
    if isinstance(op, PackTwoT):
        return (PackTwo(op.pair_axis),)
    if isinstance(op, SplitPairsT):
        return (SplitPairs(op.pair_axis),)
    if isinstance(op, UnpackTwoT):
        return (UnpackTwo(op.pair_axis, op.z_axis, op.impl_stage),)
    if isinstance(op, RepackHalvesT):
        return (RepackHalves(op.pair_axis, op.nz, op.z_axis, op.impl_stage),)
    raise ScheduleError(f"no adjoint rule for stage op {op.describe()}")


# ---------------------------------------------------------------------------
# the Schedule -> Schedule transform
# ---------------------------------------------------------------------------

def _renum(op: StageOp, k: int) -> StageOp:
    """Retarget an op's per-stage impl selector at its adjoint slot."""
    if hasattr(op, "impl_stage"):
        return dataclasses.replace(op, impl_stage=k)
    return op


def _chunk_hazards(unit: dict) -> set:
    """Axes a stage with this compute unit must NOT be K-chunked along.

    The executor chunks the whole prologue->fft->epilogue chain, so the
    chunk axis may not be the FFT axis, nor an axis a pack-family op
    slices/concatenates (its pair axis, and the z spectrum axis for the
    folded unpack/repack pair).  A fused k-space multiply consumes a
    full-block operand, so a stage carrying one is never chunkable.
    """
    hz = set()
    if unit["fft_axis"] is not None:
        hz.add(unit["fft_axis"])
    for op in unit["prologue"] + unit["epilogue"]:
        if isinstance(op, SpectralScale):
            hz |= {0, 1, 2}
        if hasattr(op, "pair_axis"):
            hz.add(op.pair_axis)
        if hasattr(op, "z_axis"):
            hz.add(op.z_axis)
    return hz


def adjoint_schedule(sched: Schedule) -> Schedule:
    """The linear transpose of ``sched`` as a first-class schedule.

    Maps cotangents of the forward *output* layout to cotangents of the
    forward *input* layout, reusing the forward plan's communicators,
    chunk axes and (renumbered) per-stage impl choices.  Raises
    :class:`ScheduleError` if the transposed pipeline fails layout
    propagation or does not land back on the forward input layout.
    """
    # compute unit of one forward stage, transposed: the stage chain is
    # prologue -> fft -> epilogue, so its transpose runs the transposed
    # epilogue ops (reversed) -> the same-sign fft -> the transposed
    # prologue ops (reversed).
    def compute_t(st: Stage):
        pro = []
        for op in reversed(st.epilogue):
            pro.extend(adjoint_ops(op))
        epi = []
        for op in reversed(st.prologue):
            epi.extend(adjoint_ops(op))
        if st.fft_axis is None and not pro and not epi:
            return None
        return dict(name=f"adj-{st.name}", fft_axis=st.fft_axis,
                    prologue=tuple(pro), epilogue=tuple(epi))

    def comm_t(st: Stage) -> dict:
        # transposed tiled all_to_all: same communicator, split<->concat
        # swapped; the chunk axis is uninvolved in {split, concat} (an
        # unchanged set), so it stays valid for the adjoint's K-chunking.
        # Per-stage impl/K overrides (searched schedules) ride along: the
        # adjoint of a ring stage is a ring stage over the same wire.
        return dict(comm_axis=st.comm_axis, split_axis=st.concat_axis,
                    concat_axis=st.split_axis, chunk_axis=st.chunk_axis,
                    transpose_impl=st.transpose_impl, overlap_k=st.overlap_k)

    stages = []
    # the terminal epilogue transposes into ops that run FIRST
    lead = []
    for op in reversed(sched.epilogue):
        lead.extend(adjoint_ops(op))
    pending = (dict(name="adj-epilogue", fft_axis=None,
                    prologue=tuple(lead), epilogue=())
               if lead else None)
    for st in reversed(sched.stages):
        if st.comm_axis is not None:
            # this stage's transposed comm executes before its transposed
            # compute: it terminates whatever compute is pending — unless
            # the forced chunk axis (the one axis uninvolved in the
            # transpose) is hazardous for that compute, in which case the
            # compute flushes separately and the comm rides alone
            if pending is not None and st.chunk_axis in _chunk_hazards(pending):
                stages.append(Stage(**pending))
                pending = None
            base = pending or dict(name=f"adj-comm-{st.name}", fft_axis=None,
                                   prologue=(), epilogue=())
            stages.append(Stage(**base, **comm_t(st)))
            pending = None
        unit = compute_t(st)
        if unit is not None:
            if pending is not None:
                stages.append(Stage(**pending))
            pending = unit
    if pending is not None:
        stages.append(Stage(**pending))

    # renumber fft stages 0..2 in adjoint execution order so per-stage
    # local_impl / overlap_mode tuples index naturally
    out, k = [], 0
    for st in stages:
        if st.fft_axis is not None:
            st = dataclasses.replace(
                st, impl_stage=k,
                prologue=tuple(_renum(op, k) for op in st.prologue),
                epilogue=tuple(_renum(op, k) for op in st.epilogue))
            k += 1
        out.append(st)

    extra = tuple(dataclasses.replace(ec, name=f"adj-{ec.name}")
                  for ec in sched.extra_comms)
    adj = Schedule(f"{sched.name}^T", sched.sign, sched.layout_out,
                   tuple(out), extra_comms=extra)
    if str(adj.layout_out) != str(sched.layout_in):
        raise ScheduleError(
            f"adjoint of {sched.name} does not restore the input layout: "
            f"{adj.layout_out} != {sched.layout_in}")
    return adj


# ---------------------------------------------------------------------------
# out-of-body plane transposes (packed pipeline's DC/Nyquist fold/unfold)
# ---------------------------------------------------------------------------

def _herm2(p: jax.Array) -> jax.Array:
    """0.5 * (p + conj(p[-kx, -ky])): self-transpose 2-D Hermitian part."""
    return 0.5 * (p + jnp.conj(packing.negate_freq(
        packing.negate_freq(p, -1), -2)))


@scopes.role(scopes.RELAYOUT)
def unfold_dc_plane_t(ct: jax.Array) -> jax.Array:
    """Transpose of :func:`repro.real.pipeline.unfold_dc_plane`:
    rfftn-shaped cotangent (..., Nz2 + 1) -> packed cotangent (..., Nz2)
    with bin 0 = Herm2(ct[0]) - i * Herm2(ct[Nz2])."""
    nz2 = ct.shape[-1] - 1
    g = _herm2(ct[..., 0]) - 1j * _herm2(ct[..., nz2])
    return jnp.concatenate([g[..., None], ct[..., 1:nz2]], axis=-1)


@scopes.role(scopes.RELAYOUT)
def fold_dc_plane_t(pbar: jax.Array, nz: int) -> jax.Array:
    """Transpose of :func:`repro.real.pipeline.fold_dc_plane`: packed
    cotangent (..., Nz2) -> rfftn-shaped cotangent (..., Nz2 + 1)."""
    p0 = pbar[..., 0]
    y0 = _herm2(p0)
    yn = 0.5j * (p0 - jnp.conj(packing.negate_freq(
        packing.negate_freq(p0, -1), -2)))
    return jnp.concatenate([y0[..., None], pbar[..., 1:], yn[..., None]],
                           axis=-1)
