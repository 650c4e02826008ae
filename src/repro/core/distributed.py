"""Distributed 3-D FFT entry points: build a stage schedule, run it.

Mapping from the paper's MPI+OpenMP design to JAX/XLA (DESIGN.md §2):

  row/column MPI communicators  ->  mesh axes inside ``shard_map``
  MPI_Alltoall                  ->  ``jax.lax.all_to_all`` (split/concat axes
                                    express the pack/unpack steps 2,4,6,8)
  OpenMP comm thread + K chunks ->  K independent (FFT chunk -> transpose)
                                    chains, emitted as a depth-1 software
                                    pipeline (``overlap_mode="pipelined"``:
                                    chunk i+1's FFT precedes chunk i's
                                    collective in program order, so overlap
                                    is structural, not a scheduling
                                    accident).  K=1 reproduces options 1/2
                                    (no overlap), K>=2 options 3/4 (CROFT
                                    default K=2, paper §5.1).
                                    ``transpose_impl="ring"`` additionally
                                    decomposes each transpose into P-1
                                    independent ppermute rounds with fused
                                    Pallas pack/unpack
                                    (``kernels/transpose_pack.py``) — the
                                    explicit pack->send->unpack pipeline.
  FFTW plan reuse               ->  plan-constant caching (plan.py); disabled
                                    = "multiple plans" options 1/3.

Since the schedule refactor the pipeline itself is *data*, not code: the
pencil / slab / cell bodies are built by ``repro.core.schedule.build_c2c``
(a pure ``Decomposition -> Schedule`` function), executed by the single
``schedule.run_schedule`` executor (which owns K-chunked overlap,
per-stage ``local_impl`` and batch-axis offsetting), and *the same
objects* are walked by the autotuner's cost model — see ``schedule.py``
for the IR and the README "Architecture" section for the data flow.
This module keeps the user-facing knobs (:class:`FFTOptions`) and the
``shard_map`` wrappers (sharding specs are derived from the schedule's
symbolic layouts).

The FFTW3 baseline the paper benchmarks against is represented two ways:
slab decomposition (its scaling model) and ``transpose_impl="pairwise"``
(its communication pattern: P-1 *blocking* sendrecv exchanges placed
through a serial chain, reproducing the "864 calls vs 64 calls" profile
of figs 12-15).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Callable, Optional, Sequence, Union

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import local_fft
from repro.core import schedule as schedule_lib
from repro.core.decomposition import Decomposition

@dataclasses.dataclass(frozen=True)
class FFTOptions:
    """Knobs reproducing the paper's option matrix (§5.1) plus extensions.

    overlap_k      CROFT's K: chunks per (FFT -> all_to_all) stage. 1 = no
                   overlap (options 1/2); 2 = CROFT's shipped default.
    plan_cache     True = "single plan" (options 2/4); False = re-materialize
                   twiddles per call ("multiple plans", options 1/3).
    local_impl     "matmul" (four-step, MXU-native) | "stockham" | "xla"
                   | "pallas" (four-step Pallas kernel); or a 3-tuple of
                   those, one per pipeline stage in execution order (the
                   i-th 1-D FFT of the pipeline uses local_impl[i] — e.g.
                   matmul on the contiguous first axis, Stockham on the
                   strided later ones).  A homogeneous tuple collapses to
                   its single value (canonical form for wisdom keys).
    output_layout  "natural" (paper: restore the input pencil layout with two
                   reverse transposes) | "spectral" (beyond-paper: stay in
                   z-pencil layout, halving collective bytes).
    transpose_impl "alltoall" (one fused collective) | "ring" (P-1
                   independent ppermute rounds with fused Pallas
                   pack/unpack — the explicit overlap pipeline) |
                   "pairwise" (FFTW3-style serial-chain emulation).
                   ring/pairwise ppermute over single mesh axes only —
                   folded axes and the cell regroup communicator are
                   rejected by ``Decomposition.validate``.
    overlap_mode   how K >= 2 chunks are emitted: "pipelined" (staged
                   software pipeline — chunk i+1's FFT precedes chunk
                   i's collective in program order, the explicit overlap
                   engine) | "unrolled" (legacy chunk-after-chunk
                   emission, overlap left to XLA's async scheduler); or
                   a 3-tuple of those, one per pipeline stage (indexed
                   like ``local_impl``).  Both orders run identical ops,
                   so results are bitwise equal.
    """

    overlap_k: int = 2
    plan_cache: bool = True
    local_impl: Union[str, tuple] = "matmul"
    output_layout: str = "natural"
    transpose_impl: str = "alltoall"
    overlap_mode: Union[str, tuple] = "pipelined"

    TRANSPOSE_IMPLS = ("alltoall", "ring", "pairwise")
    OVERLAP_MODES = ("pipelined", "unrolled")

    def __post_init__(self):
        object.__setattr__(self, "local_impl",
                           _canon_stage_tuple("local_impl", self.local_impl))
        om = _canon_stage_tuple("overlap_mode", self.overlap_mode)
        for m in (om if isinstance(om, tuple) else (om,)):
            if m not in self.OVERLAP_MODES:
                raise ValueError(f"overlap_mode must be one of "
                                 f"{self.OVERLAP_MODES}, got {m!r}")
        object.__setattr__(self, "overlap_mode", om)
        if self.transpose_impl not in self.TRANSPOSE_IMPLS:
            raise ValueError(f"transpose_impl must be one of "
                             f"{self.TRANSPOSE_IMPLS}, got "
                             f"{self.transpose_impl!r}")

    # -- canonical string form (plan-cache / wisdom keys) -------------------
    def to_token(self) -> str:
        """Canonical string form covering EVERY knob that changes the
        compiled executable — the plan-cache key fragment.  Per-stage
        3-tuples join with ``-`` (impl/mode names contain no ``-``), e.g.
        ``k2/matmul-stockham-xla/natural/ring/pipelined-unrolled-unrolled``
        with ``/noplan`` appended when ``plan_cache=False``.  Round trips
        through :meth:`from_token` (``__post_init__`` re-canonicalizes,
        so token -> options -> token is the identity)."""
        def join(v):
            return "-".join(v) if isinstance(v, tuple) else v
        tok = (f"k{self.overlap_k}/{join(self.local_impl)}/"
               f"{self.output_layout}/{self.transpose_impl}/"
               f"{join(self.overlap_mode)}")
        if not self.plan_cache:
            tok += "/noplan"
        return tok

    @classmethod
    def from_token(cls, token: str) -> "FFTOptions":
        """Inverse of :meth:`to_token`."""
        parts = token.split("/")
        plan_cache = True
        if parts and parts[-1] == "noplan":
            plan_cache = False
            parts = parts[:-1]
        if len(parts) != 5 or not parts[0].startswith("k"):
            raise ValueError(f"malformed FFTOptions token {token!r}")

        def split(v):
            items = v.split("-")
            return tuple(items) if len(items) > 1 else v
        return cls(overlap_k=int(parts[0][1:]), local_impl=split(parts[1]),
                   output_layout=parts[2], transpose_impl=parts[3],
                   overlap_mode=split(parts[4]), plan_cache=plan_cache)

    def stage_impl(self, stage: int) -> str:
        """Local 1-D implementation for the given pipeline stage."""
        if isinstance(self.local_impl, tuple):
            return self.local_impl[stage]
        return self.local_impl

    def stage_overlap(self, stage: int) -> str:
        """Chunk emission mode for the given pipeline stage."""
        if isinstance(self.overlap_mode, tuple):
            return self.overlap_mode[stage]
        return self.overlap_mode

    @classmethod
    def paper_option(cls, opt: int, **kw) -> "FFTOptions":
        """CROFT paper options 1-4 (§5.1)."""
        table = {
            1: dict(overlap_k=1, plan_cache=False),
            2: dict(overlap_k=1, plan_cache=True),
            3: dict(overlap_k=2, plan_cache=False),
            4: dict(overlap_k=2, plan_cache=True),  # shipped CROFT
        }
        return cls(**{**table[opt], **kw})


def _canon_stage_tuple(name: str, value: Union[str, tuple]) -> Union[str, tuple]:
    """Canonicalize a per-stage knob: 3-tuples collapse to their single
    value when homogeneous (the canonical form for wisdom keys)."""
    if isinstance(value, (list, tuple)):
        value = tuple(value)
        if len(value) != 3:
            raise ValueError(
                f"per-stage {name} needs exactly 3 entries, got {value}")
        if len(set(value)) == 1:
            value = value[0]
    return value


# ---------------------------------------------------------------------------
# public entry points
# ---------------------------------------------------------------------------

def _norm_scale(shape: Sequence[int], sign: int,
                norm: Optional[str]) -> Optional[float]:
    """Global normalization factor (None = no scaling at this call)."""
    nxyz = shape[-3] * shape[-2] * shape[-1]
    if norm == "ortho":
        return 1.0 / math.sqrt(nxyz)
    if (norm is None or norm == "backward") and sign == +1:
        return 1.0 / nxyz
    return None


def build_schedule(decomp: Decomposition, opts: FFTOptions,
                   sign: int = -1) -> schedule_lib.Schedule:
    """The c2c schedule ``distributed_fft3d`` will run for this plan
    (public hook for golden tests / inspection / the cost model)."""
    from_spectral = opts.output_layout == "spectral" and sign == +1
    return schedule_lib.build_c2c(decomp, sign=sign,
                                  output_layout=opts.output_layout,
                                  from_spectral=from_spectral)


def inverse_schedule(sched: schedule_lib.Schedule) -> schedule_lib.Schedule:
    """The unnormalized inverse of a pure c2c schedule.

    The adjoint transform reverses the pipeline (every transpose swaps
    split/concat; per-stage impl/K overrides ride along) and a 1-D DFT
    matrix is symmetric, so the adjoint with the sign flipped *is* the
    inverse up to the 1/N factor the caller applies via ``norm``.  This
    is how searched schedules — which have no fixed inverse builder —
    get their ``ifft``.  Restricted to pure complex pipelines: packing
    prologues/epilogues and out-of-body reshards are not sign-symmetric.
    """
    if any(st.prologue or st.epilogue for st in sched.stages) \
            or sched.epilogue or sched.extra_comms:
        raise ValueError("inverse_schedule covers pure c2c schedules only")
    from repro.grad.adjoint import adjoint_schedule
    adj = adjoint_schedule(sched)
    return dataclasses.replace(adj, name=f"{sched.name}^-1",
                               sign=-sched.sign, points=None)


def scheduled_fft3d(x: jax.Array, mesh: Mesh,
                    sched: schedule_lib.Schedule,
                    opts: Optional[FFTOptions] = None,
                    norm: Optional[str] = None,
                    kspace_filter: Optional[jax.Array] = None) -> jax.Array:
    """Run a prebuilt :class:`~repro.core.schedule.Schedule` — the entry
    point for searched pipelines, which exist only as schedule objects.

    Same contract as :func:`distributed_fft3d` (vjp-routed, plan-cached
    via ``grad_vjp.linear_plan``, optional fused k-space filter), minus
    the fixed-builder step: shardings come from the schedule's own
    symbolic layouts.
    """
    if opts is None:
        opts = FFTOptions()
    if x.ndim < 3:
        raise ValueError("scheduled_fft3d expects a (..., Nx, Ny, Nz) array")
    scale = _norm_scale(x.shape, sched.sign, norm)
    return _run_plan(x, mesh, sched, opts, scale, kspace_filter)


def _run_plan(x, mesh, sched, opts, scale, kspace_filter):
    """The plan-cached, vjp-routed run of ``sched`` on ``x``, whose axes
    before the last three are independent fields carried through the
    schedule (one collective per transpose for all of them)."""
    from repro.grad import vjp as grad_vjp
    nbatch = x.ndim - 3
    if kspace_filter is None:
        return grad_vjp.linear_plan(mesh, sched, opts, scale,
                                    nbatch).apply(x)
    plan = grad_vjp.filtered_plan(mesh, sched, opts, scale, nbatch)
    return plan(x, kspace_filter.astype(x.dtype))


def distributed_fft3d(x: jax.Array, mesh: Mesh, decomp: Decomposition,
                      sign: int = -1, opts: Optional[FFTOptions] = None,
                      norm: Optional[str] = None,
                      kspace_filter: Optional[jax.Array] = None) -> jax.Array:
    """3-D FFT of a globally-sharded (..., Nx, Ny, Nz) array; leading axes
    are independent fields, unsharded, carried natively through one run.

    Builds the decomposition's :class:`~repro.core.schedule.Schedule` and
    runs it under ``shard_map``; in/out shardings come from the schedule's
    symbolic layouts.  ``kspace_filter`` fuses a pointwise k-space
    multiply into the transform as a terminal schedule epilogue (the
    filter must be shaped/sharded like the output spectrum).
    """
    if opts is None:
        opts = FFTOptions()
    if x.ndim < 3:
        raise ValueError("distributed_fft3d expects a (..., Nx, Ny, Nz) array")
    decomp.validate(x.shape, mesh, opts.overlap_k, opts.transpose_impl)

    sched = build_schedule(decomp, opts, sign)
    # normalization uses *global* sizes; the vjp plan folds the scalar in
    # on local blocks (and reuses the same scale for the backward pass)
    scale = _norm_scale(x.shape, sign, norm)

    # route through repro.grad so jax.grad runs the adjoint schedule
    # instead of XLA differentiating the shard_map body; primal ops are
    # identical to running the schedule directly
    return _run_plan(x, mesh, sched, opts, scale, kspace_filter)


def fft3d(x, mesh=None, decomp=None, opts: Optional[FFTOptions] = None,
          norm: Optional[str] = None,
          kspace_filter: Optional[jax.Array] = None):
    """Forward 3-D FFT; single-device fallback when no mesh is given."""
    if opts is None:
        opts = FFTOptions()
    if mesh is None or math.prod(mesh.devices.shape) == 1:
        y = local_fft.fft3d_local(x, -1, impl=opts.local_impl,
                                  plan_cache=opts.plan_cache, norm=norm)
        if kspace_filter is not None:
            from repro.kernels import spectral_scale as ss
            y = ss.spectral_scale(y, kspace_filter.astype(y.dtype))
        return y
    return distributed_fft3d(x, mesh, decomp, -1, opts, norm, kspace_filter)


def ifft3d(x, mesh=None, decomp=None, opts: Optional[FFTOptions] = None,
           norm: Optional[str] = "backward"):
    """Inverse 3-D FFT (paper eq. 2: 1/(NxNyNz) normalization)."""
    if opts is None:
        opts = FFTOptions()
    if mesh is None or math.prod(mesh.devices.shape) == 1:
        return local_fft.fft3d_local(x, +1, impl=opts.local_impl,
                                     plan_cache=opts.plan_cache, norm=norm)
    return distributed_fft3d(x, mesh, decomp, +1, opts, norm)
