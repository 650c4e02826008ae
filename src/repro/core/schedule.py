"""Stage-schedule IR: one declarative representation of the FFT pipeline.

The paper's pipeline (§4.1 steps 1-9, overlapped via K chunks) used to be
hardcoded per decomposition in ``core/distributed.py``, again for the
packed real transform in ``real/pipeline.py``, and shadow-modeled a third
time by the tuner's cost model.  P3DFFT treats decomposition pipelines as
*data* — a framework enumerating layouts and exchange sequences — and
OpenFFT tunes exactly such schedule-level choices per problem.  This
module does the same for the JAX port:

  ``Stage``      one pipeline step: optional prologue ops, an optional
                 local 1-D FFT, optional epilogue ops, and an optional
                 global transpose (all_to_all over one communicator),
                 K-chunked along an uninvolved axis for overlap.
  ``Layout``     symbolic local-block layout: which mesh axes shard each
                 grid dimension, static divisors (the packed half
                 spectrum), and real/complex dtype class.  Schedules
                 propagate layouts through every stage at build time, so
                 malformed pipelines fail *before* tracing and the cost
                 model can read per-stage bytes without re-deriving
                 stage structure from ``Decomposition.kind``.
  ``Schedule``   an ordered stage list + terminal epilogue ops (e.g. the
                 fused k-space multiply, ``with_epilogue``) + metadata
                 for collectives that happen outside the shard_map body
                 (the packed pipeline's z-localizing reshard).
  ``run_schedule``  the single executor: owns K-chunked overlap, the
                 chunk-indivisible fallback (``effective_k``), per-stage
                 ``local_impl`` selection, and batch-axis offsetting
                 (leading unsharded batch dims shift every axis index).
                 It carries the block as stacked real/imaginary planes
                 (``local_fft.to_planes``: a leading plane axis of size
                 2, or 1 for a real block), so a transpose moves both
                 planes in one collective and a ``matmul`` FFT stage is
                 one real contraction (``local_fft.fft_planes``).  Stage
                 ops take that form; an op with no planes form converts
                 at the op (:func:`complex_form`).

Builders are pure functions ``Decomposition x problem x layout ->
Schedule``: :func:`build_c2c` here covers every complex pipeline
(pencil / slab / cell, natural / spectral, forward / from-spectral);
``repro.real.pipeline.build_packed_forward/inverse`` build the packed
two-for-one real pipelines (pencil and slab) on the same IR.  The tuner
(``repro.tuning.cost_model``) walks these same objects, so candidate
scoring and execution can never drift apart.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp

from repro.core import local_fft
from repro.obs import scopes

AxisName = Union[str, tuple]

_DIMS = ("x", "y", "z")


class ScheduleError(ValueError):
    """A builder produced an inconsistent pipeline (caught at build time)."""


def _flat(axis) -> tuple:
    """Flatten a (possibly nested-folded) mesh axis spec to bare names."""
    if isinstance(axis, tuple):
        out = []
        for a in axis:
            out.extend(_flat(a))
        return tuple(out)
    return (axis,)


def _axis_size(axis: AxisName) -> int:
    """Size of a (possibly folded) mesh axis from inside shard_map."""
    if isinstance(axis, tuple):
        return math.prod(jax.lax.axis_size(a) for a in _flat(axis))
    return jax.lax.axis_size(axis)


def _axis_str(axis: AxisName) -> str:
    return "+".join(_flat(axis))


# ---------------------------------------------------------------------------
# symbolic layouts
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LayoutAxis:
    """One grid dimension of a local block.

    local extent = shape[dim] / prod(mesh axis sizes of ``shards``) / den
    (``den`` is the static divisor of e.g. the packed Nz/2 half spectrum
    or the paired axis while two pencils share one complex transform).
    """

    dim: str                      # "x" | "y" | "z"
    shards: tuple = ()            # flat mesh-axis names sharding this dim
    den: int = 1

    def local_extent(self, n: int, sizes) -> int:
        return n // math.prod(sizes[s] for s in self.shards) // self.den

    def __str__(self) -> str:
        s = f"N{self.dim}"
        if self.den != 1:
            s += f":{self.den}"
        for name in self.shards:
            s += f"/{name}"
        return s


@dataclasses.dataclass(frozen=True)
class Layout:
    """Symbolic local-block layout (three grid dims + dtype class)."""

    axes: tuple                   # (LayoutAxis, LayoutAxis, LayoutAxis)
    real: bool = False

    def local_shape(self, shape: Sequence[int], axis_sizes) -> tuple:
        sizes = dict(axis_sizes)
        return tuple(a.local_extent(n, sizes)
                     for a, n in zip(self.axes, shape[-3:]))

    def elems(self, shape: Sequence[int], axis_sizes) -> int:
        return math.prod(self.local_shape(shape, axis_sizes))

    def bytes(self, shape: Sequence[int], axis_sizes,
              complex_itemsize: int = 8) -> int:
        item = complex_itemsize // 2 if self.real else complex_itemsize
        return self.elems(shape, axis_sizes) * item

    def partition_spec(self):
        from jax.sharding import PartitionSpec as P
        entries = []
        for a in self.axes:
            if not a.shards:
                entries.append(None)
            elif len(a.shards) == 1:
                entries.append(a.shards[0])
            else:
                entries.append(tuple(a.shards))
        return P(*entries)

    # -- transforms used by the schedule propagation ------------------------
    def after_all_to_all(self, comm_axis: AxisName, split_axis: int,
                         concat_axis: int) -> "Layout":
        """The concat dim loses the communicator's shards (its local extent
        grows), the split dim gains them — a global transpose."""
        names = _flat(comm_axis)
        axes = list(self.axes)
        cat = axes[concat_axis]
        missing = [n for n in names if n not in cat.shards]
        if missing:
            raise ScheduleError(
                f"all_to_all over {names} concatenates dim {cat.dim!r} which "
                f"is not sharded by {missing} (layout {self})")
        axes[concat_axis] = dataclasses.replace(
            cat, shards=tuple(s for s in cat.shards if s not in names))
        spl = axes[split_axis]
        axes[split_axis] = dataclasses.replace(spl, shards=spl.shards + names)
        return dataclasses.replace(self, axes=tuple(axes))

    def with_den(self, axis: int, mul: int = 1, div: int = 1) -> "Layout":
        axes = list(self.axes)
        a = axes[axis]
        den = a.den * mul
        if den % div:
            raise ScheduleError(f"cannot divide den={den} of {a} by {div}")
        axes[axis] = dataclasses.replace(a, den=den // div)
        return dataclasses.replace(self, axes=tuple(axes))

    def check_fft_axis(self, axis: int) -> None:
        a = self.axes[axis]
        if a.shards:
            raise ScheduleError(
                f"FFT along dim {a.dim!r} while it is sharded by {a.shards} "
                f"(layout {self})")

    def __str__(self) -> str:
        tag = "R" if self.real else "C"
        return tag + "(" + ", ".join(str(a) for a in self.axes) + ")"


def layout_for(decomp, which: str = "natural", real: bool = False) -> Layout:
    """The :class:`Layout` of a decomposition's natural/spectral spec."""
    spec = (decomp.partition_spec() if which == "natural"
            else decomp.spectral_spec())
    axes = tuple(
        LayoutAxis(dim, () if entry is None else _flat(entry))
        for dim, entry in zip(_DIMS, spec))
    return Layout(axes, real=real)


# ---------------------------------------------------------------------------
# stage ops (prologue/epilogue): declarative, layout-aware
# ---------------------------------------------------------------------------

class StageOp:
    """Protocol for prologue/epilogue ops.

    ``apply`` runs inside the executor (per K-chunk for chunked stages)
    on its planes block, axis indices offset by ``off`` (the plane axis
    and any batch axes);
    ``transform`` propagates the symbolic layout; ``describe`` renders the
    op for golden snapshots.  Heavy imports happen lazily inside ``apply``
    so the IR stays importable from anywhere (core <-> real <-> kernels).
    """

    def apply(self, blk, opts, ctx, off: int):
        raise NotImplementedError

    def transform(self, layout: Layout) -> Layout:
        return layout

    def describe(self) -> str:
        return type(self).__name__


def complex_form(apply):
    """Decorator for the ``apply`` of a stage op written for complex (or
    plain real) blocks: the executor's planes convert to that form at the
    op and back after it, under ``croft.relayout``."""
    @functools.wraps(apply)
    def on_planes(self, blk, opts, ctx, off):
        out = apply(self, local_fft.from_planes(blk), opts, ctx, off - 1)
        return local_fft.to_planes(out)
    return on_planes


@dataclasses.dataclass(frozen=True)
class PackTwo(StageOp):
    """Pair two real pencils along ``pair_axis`` into one complex block."""

    pair_axis: int

    def apply(self, blk, opts, ctx, off):
        from repro.real import packing
        return packing.pack_two_planes(blk, self.pair_axis + off)

    def transform(self, layout):
        if not layout.real:
            raise ScheduleError("pack2 needs a real block")
        return dataclasses.replace(
            layout.with_den(self.pair_axis, mul=2), real=False)

    def describe(self):
        return f"pack2[{_DIMS[self.pair_axis]}]"


@dataclasses.dataclass(frozen=True)
class UnpackTwo(StageOp):
    """Split the packed z spectrum into two folded half spectra (the
    shard-aligned Nz/2-bin layout, Nyquist folded into DC)."""

    pair_axis: int
    z_axis: int = 2
    impl_stage: int = 0

    def apply(self, blk, opts, ctx, off):
        from repro.real import packing
        use_pallas = opts.stage_impl(self.impl_stage) == "pallas"
        return packing.unpack_two_planes(blk, self.pair_axis + off,
                                         use_pallas=use_pallas)

    def transform(self, layout):
        return layout.with_den(self.pair_axis, div=2).with_den(
            self.z_axis, mul=2)

    def describe(self):
        return f"unpack2[{_DIMS[self.pair_axis]}]"


@dataclasses.dataclass(frozen=True)
class RepackHalves(StageOp):
    """Inverse of :class:`UnpackTwo`: rebuild the full packed z spectrum."""

    pair_axis: int
    nz: int
    z_axis: int = 2
    impl_stage: int = 2

    def apply(self, blk, opts, ctx, off):
        from repro.real import packing
        use_pallas = opts.stage_impl(self.impl_stage) == "pallas"
        return packing.repack_halves_planes(blk, self.pair_axis + off,
                                            self.nz, use_pallas=use_pallas)

    def transform(self, layout):
        return layout.with_den(self.pair_axis, mul=2).with_den(
            self.z_axis, div=2)

    def describe(self):
        return f"repack2[{_DIMS[self.pair_axis]}]"


@dataclasses.dataclass(frozen=True)
class SplitPairs(StageOp):
    """Complex block -> real block, doubled along ``pair_axis``."""

    pair_axis: int

    def apply(self, blk, opts, ctx, off):
        from repro.real import packing
        return packing.split_pairs_planes(blk, self.pair_axis + off)

    def transform(self, layout):
        if layout.real:
            raise ScheduleError("split2 needs a complex block")
        return dataclasses.replace(
            layout.with_den(self.pair_axis, div=2), real=True)

    def describe(self):
        return f"split2[{_DIMS[self.pair_axis]}]"


@dataclasses.dataclass(frozen=True)
class SpectralScale(StageOp):
    """Fused k-space multiply: ``blk * alpha * operands[key]``.

    Attached via :meth:`Schedule.with_epilogue`; the filter block arrives
    through the executor's ``operands`` mapping sharded like the layout at
    the attachment point (``Schedule.layout_out`` for terminal epilogues).
    """

    key: str = "filter"
    alpha: float = 1.0

    def apply(self, blk, opts, ctx, off):
        if self.key not in ctx:
            raise ScheduleError(
                f"schedule epilogue needs operand {self.key!r}; pass it via "
                "run_schedule(..., operands={...})")
        from repro.kernels import spectral_scale as ss
        return ss.spectral_scale_stacked(blk, ctx[self.key], self.alpha)

    def describe(self):
        return f"kscale[{self.key}]"


# ---------------------------------------------------------------------------
# stages and schedules
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Stage:
    """One pipeline step (paper steps {1,2,3} / {5,6,7} as one unit).

    Executed as: prologue ops -> local FFT along ``fft_axis`` (if any,
    using ``opts.stage_impl(impl_stage)``) -> epilogue ops -> all_to_all
    over ``comm_axis`` (if any).  When a communicator is present the whole
    chain is split into K chunks along ``chunk_axis`` (an axis not
    involved in the transpose): chunk i's collective has no data
    dependence on chunk i+1's FFT, so XLA's async collective scheduler
    overlaps them — the paper's second OpenMP thread.

    ``transpose_impl`` / ``overlap_k`` are *per-stage* overrides of the
    same-named :class:`FFTOptions` knobs (None = inherit).  They are what
    the schedule-space search tunes: ring on the small communicator,
    alltoall on the large one, different K per stage — OpenFFT's
    per-exchange pattern choice, expressed in the IR the executor runs.
    """

    name: str
    fft_axis: Optional[int] = None
    comm_axis: Optional[AxisName] = None
    split_axis: int = 0
    concat_axis: int = 0
    chunk_axis: int = 0
    impl_stage: int = 0
    prologue: tuple = ()
    epilogue: tuple = ()
    transpose_impl: Optional[str] = None
    overlap_k: Optional[int] = None


def stage_transpose_impl(st: Stage, opts) -> str:
    """The transpose implementation this stage actually runs (its own
    override when set, else the plan-wide ``opts.transpose_impl``)."""
    return st.transpose_impl if st.transpose_impl is not None \
        else opts.transpose_impl


def stage_overlap_k(st: Stage, opts) -> int:
    """The chunk count this stage actually targets (its own override when
    set, else the plan-wide ``opts.overlap_k``)."""
    return st.overlap_k if st.overlap_k is not None else opts.overlap_k


@dataclasses.dataclass(frozen=True)
class StagePoints:
    """Layouts at the four observation points of one stage."""

    entry: Layout                 # stage input (what gets K-chunked)
    fft: Layout                   # after prologue (the FFT operand)
    comm: Layout                  # after epilogue (what the a2a moves)
    out: Layout                   # after the a2a


@dataclasses.dataclass(frozen=True)
class ExtraComm:
    """A collective outside the shard_map body (metadata for the cost
    model): e.g. the packed pipeline's z-localizing epilogue reshard —
    one fused all-to-all of the half volume, never K-chunked."""

    name: str
    layout: Layout


@dataclasses.dataclass(frozen=True)
class Schedule:
    """A fully-specified pipeline: stages + terminal epilogue + metadata.

    Layouts are propagated through every stage at construction; an
    inconsistent builder (FFT along a sharded axis, transpose over a
    communicator the concat dim is not sharded by, ...) raises
    :class:`ScheduleError` immediately.
    """

    name: str
    sign: int
    layout_in: Layout
    stages: tuple
    epilogue: tuple = ()          # terminal ops, run once (never chunked)
    extra_comms: tuple = ()       # out-of-body collectives (metadata only)
    points: tuple = None          # derived; do not pass

    def __post_init__(self):
        points = []
        cur = self.layout_in
        for st in self.stages:
            entry = cur
            for op in st.prologue:
                cur = op.transform(cur)
            if st.fft_axis is not None:
                cur.check_fft_axis(st.fft_axis)
            fft = cur
            for op in st.epilogue:
                cur = op.transform(cur)
            comm = cur
            if st.comm_axis is not None:
                cur = cur.after_all_to_all(st.comm_axis, st.split_axis,
                                           st.concat_axis)
            points.append(StagePoints(entry, fft, comm, cur))
        for op in self.epilogue:
            cur = op.transform(cur)
        object.__setattr__(self, "points", tuple(points))
        object.__setattr__(self, "_layout_out", cur)

    @property
    def layout_out(self) -> Layout:
        return self._layout_out

    def with_epilogue(self, op: StageOp) -> "Schedule":
        """Attach a terminal epilogue op to the last stage (run once on the
        final block, after its collective — never per-chunk)."""
        return dataclasses.replace(self, epilogue=self.epilogue + (op,),
                                   points=None)

    # -- introspection (cost model, golden tests, effective_k) --------------
    def comm_stages(self) -> list:
        return [(i, st) for i, st in enumerate(self.stages)
                if st.comm_axis is not None]

    def transpose_count(self) -> int:
        """Global transposes per transform, including out-of-body reshards
        (the single source the tuner and ``Croft3D`` both read)."""
        return len(self.comm_stages()) + len(self.extra_comms)

    def effective_k(self, shape: Sequence[int], axis_sizes,
                    overlap_k: int) -> tuple:
        """Per-comm-stage chunk count the executor will actually use: K
        where the stage-entry extent of ``chunk_axis`` divides, else the
        silent fallback to 1 (no overlap for that stage)."""
        out = []
        for i, st in self.comm_stages():
            ext = self.points[i].entry.local_shape(shape, axis_sizes)[
                st.chunk_axis]
            k = st.overlap_k if st.overlap_k is not None else overlap_k
            out.append(k if k > 1 and ext % k == 0 else 1)
        return tuple(out)

    def fft_events(self, shape: Sequence[int], axis_sizes) -> list:
        """(impl_stage, local_elems, transform_size) per local FFT, in
        pipeline order — what the cost model charges compute for."""
        out = []
        for st, pts in zip(self.stages, self.points):
            if st.fft_axis is None:
                continue
            loc = pts.fft.local_shape(shape, axis_sizes)
            out.append((st.impl_stage, math.prod(loc), loc[st.fft_axis]))
        return out

    def comm_events(self, shape: Sequence[int], axis_sizes,
                    complex_itemsize: int = 8) -> list:
        """One dict per collective: bytes each chip injects, communicator
        size, chunkability — in-body transposes first, then out-of-body
        reshards (one fused all-to-all each, never chunked)."""
        sizes = dict(axis_sizes)
        out = []
        for i, st in self.comm_stages():
            pts = self.points[i]
            csize = math.prod(sizes[n] for n in _flat(st.comm_axis))
            out.append({
                "name": st.name,
                "bytes": pts.comm.bytes(shape, axis_sizes, complex_itemsize),
                "comm_size": csize,
                "chunkable": True,
                "chunk_extent": pts.entry.local_shape(shape, axis_sizes)[
                    st.chunk_axis],
            })
        for ec in self.extra_comms:
            out.append({
                "name": ec.name,
                "bytes": ec.layout.bytes(shape, axis_sizes, complex_itemsize),
                "comm_size": 1,
                "chunkable": False,
                "chunk_extent": 1,
            })
        return out

    def describe(self) -> str:
        """Stable text rendering (the golden-snapshot format)."""
        lines = [f"schedule {self.name} sign={self.sign:+d}",
                 f"  in : {self.layout_in}"]
        for i, (st, pts) in enumerate(zip(self.stages, self.points)):
            parts = [op.describe() for op in st.prologue]
            if st.fft_axis is not None:
                parts.append(f"fft[{_DIMS[st.fft_axis]}]@s{st.impl_stage}")
            parts.extend(op.describe() for op in st.epilogue)
            if st.comm_axis is not None:
                a2a = (f"a2a[{_axis_str(st.comm_axis)}] split={st.split_axis} "
                       f"concat={st.concat_axis} chunk={st.chunk_axis}")
                if st.transpose_impl is not None:
                    a2a += f" impl={st.transpose_impl}"
                if st.overlap_k is not None:
                    a2a += f" K={st.overlap_k}"
                parts.append(a2a)
            lines.append(f"  {i} {st.name}: " + " | ".join(parts)
                         + f" -> {pts.out}")
        for op in self.epilogue:
            lines.append(f"  + epilogue {op.describe()}")
        for ec in self.extra_comms:
            lines.append(f"  + reshard {ec.name}: {ec.layout} "
                         "(one fused all-to-all)")
        lines.append(f"  out: {self.layout_out}")
        return "\n".join(lines)


# ---------------------------------------------------------------------------
# the executor
# ---------------------------------------------------------------------------

@scopes.role(scopes.RELAYOUT)
def _pack_pieces(blk: jax.Array, axis: AxisName, split_axis: int) -> list:
    """Rotated-block pack shared by the ring and pairwise transposes.

    One fused pass (``kernels/transpose_pack.rotate_blocks``) rotates the
    P send blocks of ``split_axis`` by this rank's index, after which
    piece s — the block bound for rank ``(idx + s) % P`` — is a *static*
    slice, replacing the per-round ``dynamic_slice`` of the old path.
    """
    from repro.kernels import transpose_pack
    p = jax.lax.axis_size(axis)
    idx = jax.lax.axis_index(axis)
    return transpose_pack.pack_pieces(blk, split_axis, idx, p)


def _ring_transpose(blk: jax.Array, axis: AxisName, split_axis: int,
                    concat_axis: int) -> jax.Array:
    """P-1-round ring transpose: pack -> send -> unpack, no serial chain.

    The rounds are structurally independent (each ppermute consumes its
    own packed piece and feeds only the final concatenate), so XLA's
    async scheduler — and the staged chunk pipeline of
    :func:`run_stage` — can run round s's send while other rounds pack
    or other chunks run their local FFTs: the explicit form of the
    paper's dedicated communication thread, and the pack->send->unpack
    pipeline of Verma et al.'s multi-node GPU FFT.  Received pieces are
    reassembled with one fused rotation instead of the P-1 full-size
    ``dynamic_update_slice`` writes the pairwise emulation pays.
    """
    from repro.kernels import transpose_pack
    p = jax.lax.axis_size(axis)
    idx = jax.lax.axis_index(axis)
    pieces = _pack_pieces(blk, axis, split_axis)
    recv = [pieces[0]]                      # round 0: my own block, no comm
    with jax.named_scope(scopes.TRANSPOSE):
        for s in range(1, p):
            perm = [(i, (i + s) % p) for i in range(p)]
            recv.append(jax.lax.ppermute(pieces[s], axis, perm))
    # concat order [round 0, round P-1, ..., round 1] puts the piece from
    # src (idx + m) % P at block m; rotating by -idx restores src order.
    ordered = [recv[0]] + recv[:0:-1]
    with jax.named_scope(scopes.RELAYOUT):
        return transpose_pack.unpack_pieces(ordered, concat_axis, -idx)


def _pairwise_transpose(blk: jax.Array, axis: AxisName, split_axis: int,
                        concat_axis: int) -> jax.Array:
    """FFTW3-style emulation: P-1 *blocking* sendrecv rounds — round
    s+1's exchange is ordered after round s's completes (an
    ``optimization_barrier``, the data-flow form of MPI_Sendrecv's
    blocking semantics), and each received piece lands through a serial
    ``dynamic_update_slice`` chain.  Numerically identical to the other
    impls; this is the baseline whose serialized rounds the ring
    pipeline exists to avoid (figs 12-15).  The send side shares the
    fused rotated pack."""
    p = jax.lax.axis_size(axis)
    idx = jax.lax.axis_index(axis)
    n_cat = blk.shape[concat_axis]
    pieces = _pack_pieces(blk, axis, split_axis)
    out_shape = list(blk.shape)
    out_shape[split_axis] = pieces[0].shape[split_axis]
    out_shape[concat_axis] = n_cat * p
    with jax.named_scope(scopes.RELAYOUT):
        out = jnp.zeros(out_shape, blk.dtype)
        out = jax.lax.dynamic_update_slice_in_dim(out, pieces[0],
                                                  idx * n_cat, concat_axis)
    for s in range(1, p):
        perm = [(i, (i + s) % p) for i in range(p)]
        with jax.named_scope(scopes.TRANSPOSE):
            recv = jax.lax.ppermute(pieces[s], axis, perm)
            if s + 1 < p:
                # blocking round: the next send may not start until this
                # round's receive has completed
                pieces[s + 1], _ = jax.lax.optimization_barrier(
                    (pieces[s + 1], recv))
        src = (idx - s) % p
        with jax.named_scope(scopes.RELAYOUT):
            out = jax.lax.dynamic_update_slice_in_dim(out, recv, src * n_cat,
                                                      concat_axis)
    return out


def _all_to_all(blk: jax.Array, axis: AxisName, split_axis: int,
                concat_axis: int, impl: str = "alltoall") -> jax.Array:
    """Global transpose along one communicator.

    ``impl="alltoall"``  one fused collective (CROFT's MPI_Alltoall).
    ``impl="ring"``      P-1 independent ppermute rounds with fused
                         Pallas pack/unpack — the explicit overlap
                         pipeline (see :func:`_ring_transpose`).
    ``impl="pairwise"``  P-1 ppermute exchanges through a serial update
                         chain (FFTW3's MPI_Sendrecv pattern) —
                         numerically identical, many more collective
                         ops; used for the figs 12-15 benchmark.
    """
    if impl == "alltoall":
        with jax.named_scope(scopes.TRANSPOSE):
            return jax.lax.all_to_all(blk, axis, split_axis=split_axis,
                                      concat_axis=concat_axis, tiled=True)
    if impl not in ("ring", "pairwise"):
        raise ValueError(f"unknown transpose impl {impl!r}")
    if isinstance(axis, tuple):
        raise ValueError(f"{impl} transpose supports single mesh axes only")
    if impl == "ring":
        return _ring_transpose(blk, axis, split_axis, concat_axis)
    return _pairwise_transpose(blk, axis, split_axis, concat_axis)


def stage_pre(blk: jax.Array, st: Stage, sign: int, opts, off: int = 1,
              ctx=None) -> jax.Array:
    """The compute leg of one stage: prologue ops -> local FFT ->
    epilogue ops, on one (chunk of a) local block."""
    ctx = ctx or {}
    for op in st.prologue:
        blk = op.apply(blk, opts, ctx, off)
    if st.fft_axis is not None:
        blk = local_fft.fft_along(blk, st.fft_axis + off, sign,
                                  impl=opts.stage_impl(st.impl_stage),
                                  nbatch=off - 1, plan_cache=opts.plan_cache)
    for op in st.epilogue:
        blk = op.apply(blk, opts, ctx, off)
    return blk


def stage_comm(blk: jax.Array, st: Stage, opts, off: int = 1) -> jax.Array:
    """The collective leg of one stage (the global transpose); the
    counterpart of :func:`stage_pre`."""
    return _all_to_all(blk, st.comm_axis, st.split_axis + off,
                       st.concat_axis + off, stage_transpose_impl(st, opts))


def stage_category(st: Stage) -> str:
    """The dominant tracer category of a stage (``repro.obs.CATEGORIES``)."""
    if st.fft_axis is not None:
        return "fft"
    if st.comm_axis is not None:
        return "collective"
    if st.prologue:
        return "pack"
    return "unpack" if st.epilogue else "epilogue"


def run_stage(blk: jax.Array, st: Stage, sign: int, opts, off: int = 1,
              ctx=None) -> jax.Array:
    """Execute one stage on a local planes block (axis indices offset by
    ``off``: the plane axis and any leading batch dims).  Owns the
    K-chunked overlap and the silent fallback to one chunk when
    ``chunk_axis`` is not divisible by K.

    With K >= 2 chunks the stage runs as a depth-1 *software pipeline*
    (``opts.stage_overlap``: "pipelined", the default): chunk i+1's
    prologue/FFT is emitted *before* chunk i's collective, so the
    overlap is a structural property of the program order — chunk i's
    transpose has no consumer between it and chunk i+1's FFT — rather
    than a scheduling accident.  ``"unrolled"`` keeps the legacy
    chunk-after-chunk emission (chunk i's collective precedes chunk
    i+1's FFT only in the dependence graph, relying on XLA's async
    collective scheduler to interleave them).  Both modes run the same
    ops on the same chunks, so their outputs are bitwise identical.

    Every op lands under ``croft.stage.<name>`` (``repro.obs.scopes``),
    and chunk i's ops under ``k<i>`` inside it.
    """
    ctx = ctx or {}

    def pre(c, i=None):
        with scopes.stage(st.name, i):
            return stage_pre(c, st, sign, opts, off, ctx)

    def comm(c, i=None):
        with scopes.stage(st.name, i):
            return stage_comm(c, st, opts, off)

    if st.comm_axis is None:
        return pre(blk)  # nothing to overlap with: never chunked
    k = stage_overlap_k(st, opts)
    if k <= 1 or blk.shape[st.chunk_axis + off] % k:
        return comm(pre(blk))
    ax = st.chunk_axis + off
    with scopes.stage(st.name), jax.named_scope(scopes.RELAYOUT):
        chunks = jnp.split(blk, k, axis=ax)
    if opts.stage_overlap(st.impl_stage) == "unrolled":
        outs = [comm(pre(c, i), i) for i, c in enumerate(chunks)]
    else:
        # pipelined: double-buffered staged unroll — while chunk i is on
        # the wire, chunk i+1 is in the FFT (the paper's second OpenMP
        # thread)
        outs = []
        inflight = pre(chunks[0], 0)
        for i in range(k):
            nxt = pre(chunks[i + 1], i + 1) if i + 1 < k else None
            outs.append(comm(inflight, i))
            inflight = nxt
    with scopes.stage(st.name), jax.named_scope(scopes.RELAYOUT):
        return jnp.concatenate(outs, axis=ax)


def run_schedule(blk: jax.Array, sched: Schedule, opts,
                 operands=None) -> jax.Array:
    """Execute a schedule on a local (shard_map) block.

    The block runs as stacked real/imaginary planes
    (``local_fft.to_planes``), turned back into a complex (or real)
    block only at exit.  Leading batch axes are carried along unsharded:
    every axis index in the schedule is offset by the plane axis and
    the batch rank, ``blk.ndim - 3`` of the planes.  ``operands``
    supplies named blocks to ops that need them (e.g. the fused k-space
    filter).
    """
    with scopes.stage(sched.stages[0].name):
        blk = local_fft.to_planes(blk)
    off = blk.ndim - 3
    ctx = dict(operands or {})
    for st in sched.stages:
        blk = run_stage(blk, st, sched.sign, opts, off, ctx)
    with scopes.stage("epilogue"):
        for op in sched.epilogue:
            blk = op.apply(blk, opts, ctx, off)
        blk = local_fft.from_planes(blk)
    # Fault plane: trace-time output poisoning.  ``corrupt`` is decided
    # while tracing, so an unarmed (or unmatched) injector contributes
    # zero ops — the compiled HLO is byte-identical to a build with no
    # injector installed (pinned in tests/test_resil.py).
    from repro.resil import inject
    if inject.corrupt("exec.output", sched.name):
        blk = blk * jnp.asarray(jnp.nan, dtype=blk.dtype)
    return blk


# ---------------------------------------------------------------------------
# complex-transform builders (pencil / slab / cell)
# ---------------------------------------------------------------------------

def _pencil_stages(ax_y: AxisName, ax_z: AxisName,
                   output_layout: str) -> list:
    """Forward pencil pipeline, paper §4.1 steps 1-9 (+ optional restore)."""
    stages = [
        # steps 1-4: FFT along x, transpose x<->y in the column communicator
        Stage("x-fft+xy", fft_axis=0, impl_stage=0, comm_axis=ax_y,
              split_axis=0, concat_axis=1, chunk_axis=2),
        # steps 5-8: FFT along y, transpose y<->z in the row communicator
        Stage("y-fft+yz", fft_axis=1, impl_stage=1, comm_axis=ax_z,
              split_axis=1, concat_axis=2, chunk_axis=0),
        # step 9: FFT along z
        Stage("z-fft", fft_axis=2, impl_stage=2),
    ]
    if output_layout == "natural":
        # restore: reverse YZ then XY transposes (paper §5.2, overlapped)
        stages += [
            Stage("restore-yz", comm_axis=ax_z, split_axis=2, concat_axis=1,
                  chunk_axis=0),
            Stage("restore-xy", comm_axis=ax_y, split_axis=1, concat_axis=0,
                  chunk_axis=2),
        ]
    return stages


def build_c2c(decomp, *, sign: int = -1, output_layout: str = "natural",
              from_spectral: bool = False) -> Schedule:
    """Schedule for the complex 3-D transform of one decomposition.

    ``from_spectral`` builds the reversed pipeline consuming the spectral
    (z-local) layout and emitting the natural one — used by the inverse
    when the forward ran with ``output_layout="spectral"`` (the forward's
    restoring transposes and the inverse's leading transposes cancel).
    """
    kind = decomp.kind
    if from_spectral:
        if kind == "pencil":
            ax_y, ax_z = decomp.axes
            stages = [
                Stage("z-fft+zy", fft_axis=2, impl_stage=0, comm_axis=ax_z,
                      split_axis=2, concat_axis=1, chunk_axis=0),
                Stage("y-fft+yx", fft_axis=1, impl_stage=1, comm_axis=ax_y,
                      split_axis=1, concat_axis=0, chunk_axis=2),
                Stage("x-fft", fft_axis=0, impl_stage=2),
            ]
        elif kind == "slab":
            (ax_z,) = decomp.axes
            stages = [
                Stage("y-fft", fft_axis=1, impl_stage=0),
                Stage("z-fft+zx", fft_axis=2, impl_stage=1, comm_axis=ax_z,
                      split_axis=2, concat_axis=0, chunk_axis=1),
                Stage("x-fft", fft_axis=0, impl_stage=2),
            ]
        else:
            raise ScheduleError("cell has no spectral layout to start from")
        return Schedule(f"{kind}/c2c/from-spectral", sign,
                        layout_for(decomp, "spectral"), tuple(stages))

    if kind == "pencil":
        ax_y, ax_z = decomp.axes
        stages = _pencil_stages(ax_y, ax_z, output_layout)
    elif kind == "slab":
        (ax_z,) = decomp.axes
        stages = [
            Stage("y-fft", fft_axis=1, impl_stage=0),  # y free on both layouts
            Stage("x-fft+xz", fft_axis=0, impl_stage=1, comm_axis=ax_z,
                  split_axis=0, concat_axis=2, chunk_axis=1),
            Stage("z-fft", fft_axis=2, impl_stage=2),
        ]
        if output_layout == "natural":
            stages.append(Stage("restore-zx", comm_axis=ax_z, split_axis=2,
                                concat_axis=0, chunk_axis=1))
    else:  # cell: regroup to x-pencils over the folded (y, x) communicator
        if output_layout == "spectral":
            raise ScheduleError("cell decomposition returns natural layout "
                                "only")
        ax_x, ax_y, ax_z = decomp.axes
        fold_y = (tuple(ax_y) + _flat(ax_x) if isinstance(ax_y, tuple)
                  else (ax_y,) + _flat(ax_x))
        if len(fold_y) == 1:
            fold_y = fold_y[0]
        stages = [Stage("regroup-x", comm_axis=ax_x, split_axis=1,
                        concat_axis=0, chunk_axis=2)]
        stages += _pencil_stages(fold_y, ax_z, "natural")
        stages += [Stage("scatter-x", comm_axis=ax_x, split_axis=0,
                         concat_axis=1, chunk_axis=2)]
    return Schedule(f"{kind}/c2c/{output_layout}", sign,
                    layout_for(decomp, "natural"), tuple(stages))
