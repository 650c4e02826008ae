"""Public CROFT API: plan-style handle over the distributed 3-D FFT.

``Croft3D`` is the analogue of ``croft_parallel3d`` plus FFTW's plan object:
it binds (grid shape, mesh, decomposition, options) once, validates, and
exposes jit-compiled forward/inverse transforms.

Problem classes (FFTW-style): ``problem="c2c"`` (default) plans the
complex transform; ``problem="r2c"`` plans a real-input transform whose
forward matches ``numpy.fft.rfftn`` and whose inverse is the exact c2r
— backed by either the packed two-for-one pipeline or the embedding
fallback (``repro.real``, ``strategy=``).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import distributed, local_fft
from repro.core.decomposition import Decomposition, pencil_grid_for
from repro.core.distributed import FFTOptions
from repro.obs.tracer import get_tracer


@dataclasses.dataclass
class Croft3D:
    """A planned distributed 3-D FFT.

    >>> plan = Croft3D((1024, 1024, 1024), mesh,
    ...                Decomposition("pencil", ("data", "model")))
    >>> y = plan.forward(x)        # x sharded with plan.input_sharding
    >>> x2 = plan.inverse(y)       # == x up to dtype tolerance

    Real transforms: ``Croft3D(shape, mesh, dec, problem="r2c")`` plans
    r2c/c2r.  ``forward`` then takes a real array (see ``input_dtype`` /
    ``input_sharding`` — the packed strategy wants z-pencils) and returns
    the (Nx, Ny, Nz//2 + 1) half spectrum; ``inverse`` returns the real
    field.  ``strategy`` picks "packed" / "embed" ("auto" = packed where
    supported).
    """

    shape: tuple[int, int, int]
    mesh: Optional[Mesh] = None
    decomp: Optional[Decomposition] = None
    opts: FFTOptions = dataclasses.field(default_factory=FFTOptions)
    dtype: jnp.dtype = jnp.complex64
    #: problem class: "c2c" | "r2c" (``dtype`` is always the spectrum dtype)
    problem: str = "c2c"
    #: r2c only: "packed" | "embed" | None (= auto); resolved in __post_init__
    strategy: Optional[str] = None
    #: autotune mode ("wisdom" | "model" | "measure"); when set, the
    #: planner overrides ``decomp``/``opts`` (see ``repro.tuning``)
    tune: Optional[str] = None
    #: tune for a *training step*: the planner prices forward + adjoint
    #: schedule (problem axis "c2c_grad"/"r2c_grad") instead of forward
    #: only.  Transforms themselves are identical — gradients work on
    #: every plan (repro.grad); this only changes which plan wins.
    grad: bool = False
    wisdom_path: Optional[str] = None
    #: extra keyword arguments for ``tuning.tune`` (top_k, measure_iters, ...)
    tune_kw: Optional[dict] = None
    #: searched pipeline (``tuning.candidates.ScheduleCandidate``): when
    #: set, forward/inverse run this explicit stage list (per-stage
    #: transpose impls / K) instead of the fixed builders; ``decomp`` and
    #: ``opts`` are taken from it.  c2c only.  Set directly, or by the
    #: tune path when the planner's schedule search picks one.
    schedule: Optional[object] = None
    tune_result = None  # TuneResult when the planner picked the plan

    def __post_init__(self):
        if self.problem not in ("c2c", "r2c"):
            hint = ("; grad-aware tuning is selected with grad=True "
                    "(Croft3D.tuned(..., grad=True)), not a problem suffix"
                    if str(self.problem).endswith("_grad") else "")
            raise ValueError(f"problem must be 'c2c' or 'r2c', got "
                             f"{self.problem!r}{hint}")
        if self.tune is not None and self.mesh is None:
            raise ValueError("tune= needs a mesh (single-device plans have "
                             "nothing to tune)")
        if self.tune is not None:
            from repro import tuning
            tune_problem = self.problem + ("_grad" if self.grad else "")
            result = tuning.tune(self.shape, self.mesh, mode=self.tune,
                                 dtype=self.dtype, problem=tune_problem,
                                 wisdom_path=self.wisdom_path,
                                 **(self.tune_kw or {}))
            self.decomp, self.opts = result.decomp, result.opts
            if self.problem == "r2c":
                self.strategy = result.strategy
            self.schedule = getattr(result, "schedule", None)
            self.tune_result = result
        if self.schedule is not None:
            if self.problem != "c2c":
                raise ValueError("schedule= (a searched pipeline) plans "
                                 "the c2c problem only")
            if self.mesh is None:
                raise ValueError("schedule= needs a mesh")
            self.decomp, self.opts = self.schedule.decomp, self.schedule.opts
        if self.mesh is not None:
            if self.decomp is None:
                raise ValueError("a mesh requires a Decomposition")
            if self.schedule is not None:
                # basic mesh/axis checks at the weakest fixed-builder
                # settings, then the searched pipeline's own shape checks
                # (its transpose orders chunk along different axes than
                # the fixed pipelines, so the fixed K rules don't apply)
                self.decomp.validate(self.shape, self.mesh, 1, "alltoall")
                self.schedule.validate(self.shape, dict(self.mesh.shape))
            else:
                self.decomp.validate(self.shape, self.mesh,
                                     self.opts.overlap_k,
                                     self.opts.transpose_impl)
        if self.problem == "r2c":
            from repro import real as real_lib
            from repro.core import rfft
            self.strategy = real_lib.resolve_strategy(
                self.strategy, self.shape, self.mesh, self.decomp, self.opts)
            strat, nz = self.strategy, self.shape[-1]

            def croft_forward(v):
                return rfft.rfft3d(v, self.mesh, self.decomp, self.opts,
                                   strategy=strat)

            def croft_inverse(v):
                return rfft.irfft3d(v, nz, self.mesh, self.decomp, self.opts,
                                    strategy=strat)
        elif self.schedule is not None:
            fsched = self.schedule.build_schedule()
            isched = distributed.inverse_schedule(fsched)
            self._sched_fwd = fsched
            mesh, opts = self.mesh, self.opts

            def croft_forward(v):
                return distributed.scheduled_fft3d(v, mesh, fsched, opts)

            def croft_inverse(v):
                return distributed.scheduled_fft3d(v, mesh, isched, opts,
                                                   norm="backward")
        else:
            def croft_forward(v):
                return distributed.fft3d(v, self.mesh, self.decomp,
                                         self.opts)

            def croft_inverse(v):
                return distributed.ifft3d(v, self.mesh, self.decomp,
                                          self.opts)
        # named functions, not lambdas: the XLA module names
        # (jit_croft_forward, ...) tell the programs apart in a trace
        self._fwd = jax.jit(croft_forward)
        self._inv = jax.jit(croft_inverse)

    # -- dtypes / shapes -----------------------------------------------------
    @property
    def input_dtype(self) -> jnp.dtype:
        """What ``forward`` consumes: real for r2c, ``dtype`` for c2c."""
        if self.problem == "r2c":
            from repro.real.packing import real_dtype_for
            return jnp.dtype(real_dtype_for(self.dtype))
        return jnp.dtype(self.dtype)

    @property
    def spectrum_shape(self) -> tuple[int, int, int]:
        """Global shape of ``forward``'s output."""
        if self.problem == "r2c":
            return self.shape[:-1] + (self.shape[-1] // 2 + 1,)
        return self.shape

    # -- shardings ---------------------------------------------------------
    @property
    def input_sharding(self) -> Optional[NamedSharding]:
        if self.mesh is None:
            return None
        if self.problem == "r2c" and self.strategy == "packed":
            # packed real input is z-pencils: the r2c stage runs first,
            # so the pipeline starts where the c2c pipeline ends
            return NamedSharding(self.mesh, self.decomp.spectral_spec())
        if self.schedule is not None:
            return NamedSharding(self.mesh,
                                 self._sched_fwd.layout_in.partition_spec())
        return self.decomp.sharding(self.mesh, "natural")

    @property
    def output_sharding(self) -> Optional[NamedSharding]:
        if self.mesh is None:
            return None
        if self.problem == "r2c":
            # the (Nx, Ny, Nh) half spectrum keeps Nh = Nz//2 + 1 local
            # (it never divides the z shards); both strategies emit a
            # z-local layout, so solvers see kz unsharded.  For cell the
            # spectral spec still shards z, so mirror the guarded
            # slice's choice: x/y sharded, z replicated.
            if self.decomp.kind == "cell":
                return NamedSharding(self.mesh, P(
                    self.decomp.axes[0], self.decomp.axes[1], None))
            return NamedSharding(self.mesh, self.decomp.spectral_spec())
        if self.schedule is not None:
            # searched transpose orders can end on layouts no fixed spec
            # names (e.g. x sharded by the z communicator) — the
            # schedule's own symbolic output layout is the truth
            return NamedSharding(self.mesh,
                                 self._sched_fwd.layout_out.partition_spec())
        return self.decomp.sharding(self.mesh, self.opts.output_layout)

    def local_shape(self) -> tuple[int, ...]:
        if self.mesh is None:
            return self.shape
        return self.decomp.local_shape(self.shape, self.mesh)

    # -- transforms ----------------------------------------------------------
    #
    # Each entry opens a ``croft.<entry>`` span on the process tracer
    # (``repro.obs.tracer``): a shared null context by default, a
    # ``jax.profiler`` annotation under ``tracer.profiler_sink()``.

    def forward(self, x: jax.Array) -> jax.Array:
        with get_tracer().span("croft.forward"):
            return self._fwd(x)

    def inverse(self, y: jax.Array) -> jax.Array:
        with get_tracer().span("croft.inverse"):
            return self._inv(y)

    _fwd_filtered = None

    def _filtered_fn(self, fold: bool = False):
        """The jitted (x, h) -> filtered-spectrum callable (lazy; shared
        by :meth:`forward_filtered` and the batched dispatch path)."""
        if self._fwd_filtered is None:
            self._fwd_filtered = {}
        fn = self._fwd_filtered.get(fold)
        if fn is None:
            if self.problem == "r2c":
                from repro.core import rfft
                strat = self.strategy

                def croft_forward_filtered(v, hh):
                    return rfft.rfft3d(v, self.mesh, self.decomp, self.opts,
                                       strategy=strat, kspace_filter=hh,
                                       fold_filter=fold)
            elif fold:
                raise ValueError("fold=True is the packed r2c folded "
                                 "epilogue; c2c filters are always fused "
                                 "in-schedule")
            elif self.schedule is not None:
                mesh, opts, fsched = self.mesh, self.opts, self._sched_fwd

                def croft_forward_filtered(v, hh):
                    return distributed.scheduled_fft3d(
                        v, mesh, fsched, opts, kspace_filter=hh)
            else:
                def croft_forward_filtered(v, hh):
                    return distributed.fft3d(v, self.mesh, self.decomp,
                                             self.opts, kspace_filter=hh)
            if fold:
                croft_forward_filtered.__name__ += "_folded"
            fn = jax.jit(croft_forward_filtered)
            self._fwd_filtered[fold] = fn
        return fn

    def forward_filtered(self, x: jax.Array, h: jax.Array,
                         alpha: float = 1.0, fold: bool = False) -> jax.Array:
        """``forward`` with the k-space multiply ``alpha * h`` fused in.

        The multiply rides as a schedule epilogue (c2c: attached to the
        last stage via ``Schedule.with_epilogue``; packed r2c: fused
        right after the DC/Nyquist plane unfold) through the
        ``kernels/spectral_scale.py`` path — one jit dispatch and no
        extra HBM round trip over the spectrum.  ``h`` must be shaped
        like ``spectrum_shape`` and placed with ``output_sharding``.

        ``fold=True`` (packed r2c only) moves the multiply *before* the
        DC/Nyquist unfold, onto the packed half spectrum inside the
        schedule — one fewer pass over the spectrum, valid for filters
        with ``h(kz=0) == h(kz=Nyquist)``, that plane real and 2-D-even
        (e.g. a kz-independent low-pass over (kx, ky), or any filter
        whose DC and Nyquist kz-planes coincide).
        """
        with get_tracer().span("croft.forward_filtered"):
            hh = h if alpha == 1.0 else h * jnp.asarray(alpha, h.dtype)
            return self._filtered_fn(fold)(x, hh)

    # -- batched dispatch (the serving path) ---------------------------------
    #
    # One executable per (plan, batch-size-bucket) moving B stacked fields
    # through the SAME collective count as B=1.  c2c and the packed r2c
    # pipeline take leading batch axes natively: the executor offsets
    # every axis index by the batch rank and runs each field's DFT as one
    # batch entry of the contraction a lone field runs, so a field comes
    # out of a batch as it does alone.  The rest vmaps (under vmap the
    # per-stage all_to_alls batch into single collectives).  The c2c
    # entries donate the stacked input buffer (complex in, complex out,
    # same shape: XLA aliases it for the first stage's scratch).

    _batched = None  # lazy {(kind): jitted fn}

    def _batched_fn(self, kind: str):
        if self._batched is None:
            self._batched = {}
        fn = self._batched.get(kind)
        if fn is not None:
            return fn
        c2c = self.problem == "c2c"
        native = c2c or self.strategy == "packed"
        donate = (0,) if c2c else ()
        if kind == "forward":
            one = self._fwd

            def croft_forward_batched(v):
                return one(v) if native else jax.vmap(one)(v)
            fn = jax.jit(croft_forward_batched, donate_argnums=donate)
        elif kind == "inverse":
            one = self._inv

            def croft_inverse_batched(v):
                return one(v) if native else jax.vmap(one)(v)
            fn = jax.jit(croft_inverse_batched, donate_argnums=donate)
        elif kind == "filtered":
            one = self._filtered_fn()

            def croft_forward_filtered_batched(v, hh):
                return one(v, hh) if c2c else jax.vmap(one)(v, hh)
            fn = jax.jit(croft_forward_filtered_batched,
                         donate_argnums=donate)
        else:
            raise ValueError(f"unknown batched kind {kind!r}")
        self._batched[kind] = fn
        return fn

    def forward_batched(self, x: jax.Array) -> jax.Array:
        """``forward`` over a (B, Nx, Ny, Nz) stack — same per-stage
        collective count as B=1, results bitwise equal to B calls of
        :meth:`forward`.  c2c donates ``x``."""
        with get_tracer().span("croft.forward_batched"):
            return self._batched_fn("forward")(x)

    def inverse_batched(self, y: jax.Array) -> jax.Array:
        """``inverse`` over a (B, ...) spectrum stack (see
        :meth:`forward_batched`)."""
        with get_tracer().span("croft.inverse_batched"):
            return self._batched_fn("inverse")(y)

    def forward_filtered_batched(self, x: jax.Array,
                                 h: jax.Array) -> jax.Array:
        """:meth:`forward_filtered` over (B, ...) field and filter stacks
        (each request brings its own ``h``)."""
        with get_tracer().span("croft.forward_filtered_batched"):
            return self._batched_fn("filtered")(x, h)

    def batched_sharding(self, which: str = "input"):
        """``input_sharding``/``output_sharding`` widened with a leading
        replicated batch axis (how the service places stacked payloads)."""
        base = (self.input_sharding if which == "input"
                else self.output_sharding)
        if base is None:
            return None
        return NamedSharding(self.mesh, P(None, *base.spec))

    def release(self) -> None:
        """Drop this plan's compiled executables (compile-cache hygiene:
        the serving plan cache calls this on eviction so shape diversity
        cannot grow XLA's live-executable set without bound)."""
        fns = [self._fwd, self._inv]
        fns += list((self._fwd_filtered or {}).values())
        fns += list((self._batched or {}).values())
        for fn in fns:
            clear = getattr(fn, "clear_cache", None)
            if clear is not None:
                try:
                    clear()
                except Exception:
                    pass  # best effort: an evicted plan must never raise
        self._fwd_filtered = None
        self._batched = None

    # -- autotuning ----------------------------------------------------------
    @classmethod
    def tuned(cls, shape, mesh: Mesh, *, mode: str = "model",
              wisdom_path: Optional[str] = None, dtype=jnp.complex64,
              problem: str = "c2c", batch: int = 1, grad: bool = False,
              **tune_kw) -> "Croft3D":
        """Plan via the autotuner (``repro.tuning``) instead of hand-picked
        (decomp, opts).

        ``mode="model"`` is FFTW ESTIMATE (analytic, zero execution),
        ``mode="measure"`` is PATIENT (times the top candidates on the
        mesh), ``mode="wisdom"`` reuses a stored plan from
        ``wisdom_path`` (or $CROFT_WISDOM).  ``problem="r2c"`` plans the
        real transform (the planner also chooses the packed/embed
        strategy).  ``batch=B`` plans for B vmapped fields: the cost
        model scales volume terms by B, ``mode="measure"`` times the
        *vmapped* transform over B stacked fields, and the wisdom key
        gains a ``|b{B}`` dimension (B=1 keeps the legacy key format).
        ``grad=True`` prices a *training step*: the cost model sums the
        forward schedule and its adjoint (``repro.grad``), measurement
        times ``jax.value_and_grad`` of a scalar loss through the
        transform, and the wisdom key gains a ``|grad`` dimension — the
        chosen plan is optimal for fwd+bwd, not just inference.
        The chosen plan's provenance is on ``plan.tune_result``.
        """
        if batch != 1:
            tune_kw = dict(tune_kw, batch=batch)
        return cls(tuple(shape), mesh, dtype=jnp.dtype(dtype), tune=mode,
                   problem=problem, grad=grad, wisdom_path=wisdom_path,
                   tune_kw=tune_kw or None)

    # -- AOT artifacts for the dry-run / roofline ----------------------------
    def lower(self, entry: str = "forward"):
        """One entry point's program (``forward``, ``inverse`` or
        ``forward_filtered``), lowered at this plan's shapes and
        shardings: the program a call with arrays placed as
        ``input_sharding``/``output_sharding`` say runs.  Its compiled
        text names each instruction's ``op_name`` scopes, which a device
        trace does not carry."""
        x = jax.ShapeDtypeStruct(self.shape, self.input_dtype,
                                 sharding=self.input_sharding)
        y = jax.ShapeDtypeStruct(self.spectrum_shape, self.dtype,
                                 sharding=self.output_sharding)
        if entry == "forward":
            return self._fwd.lower(x)
        if entry == "inverse":
            return self._inv.lower(y)
        if entry == "forward_filtered":
            return self._filtered_fn().lower(x, y)
        raise ValueError(f"unknown entry {entry!r}")

    def lower_forward(self):
        return self.lower("forward")

    def candidate(self):
        """This plan's tuner-space identity: the searched
        ``ScheduleCandidate`` when one was picked, else the
        (decomp, opts) ``Candidate`` — the object the cost model, the
        tracer attribution and the serve bucket keys all read."""
        from repro.tuning.candidates import Candidate
        if self.schedule is not None:
            if self.schedule.problem == self.problem:
                return self.schedule
            return dataclasses.replace(self.schedule, problem=self.problem)
        return Candidate(self.decomp, self.opts, problem=self.problem,
                         strategy=self.strategy)

    def _forward_schedule(self):
        """The stage schedule ``forward`` executes (None when meshless) —
        the tuner's ``cost_model.schedule_for``, so this plan's roofline
        numbers and the planner's ranking read the identical object
        (including out-of-body reshards like the embedding's guarded
        half-slice)."""
        if self.mesh is None or self.decomp is None:
            return None
        from repro.tuning.cost_model import schedule_for
        return schedule_for(self.shape, self.candidate())

    def flops_model(self) -> float:
        """Analytic 5 N log2 N FLOP count for the full 3-D transform,
        summed over the schedule's local-FFT events (so the packed real
        pipeline's halved stages are charged at their true sizes)."""
        sched = self._forward_schedule()
        if sched is None:
            n_total = math.prod(self.shape)
            flops = 5.0 * n_total * sum(math.log2(s) for s in self.shape)
            if self.problem == "r2c" and self.strategy == "packed":
                flops *= 0.5
            return flops
        sizes = dict(self.mesh.shape)
        per_device = sum(5.0 * elems * math.log2(n) for _, elems, n
                         in sched.fft_events(self.shape, sizes))
        return per_device * self.decomp.n_procs(sizes)

    def comm_bytes_model(self) -> float:
        """Bytes each chip injects per transform: the sum of the
        schedule's per-stage transpose volumes plus its out-of-body
        reshards (e.g. the packed pipeline's half-volume z-localizing
        epilogue) — read from the same ``Schedule`` the executor runs."""
        sched = self._forward_schedule()
        if sched is None:
            return 0.0
        itemsize = jnp.dtype(self.dtype).itemsize
        events = sched.comm_events(self.shape, dict(self.mesh.shape),
                                   itemsize)
        return float(sum(ev["bytes"] for ev in events))


def auto_pencil(shape: Sequence[int], mesh: Mesh,
                axes: Sequence[str] = ("data", "model")) -> Decomposition:
    """Pencil decomposition over the given mesh axes (fig. 5 virtual grid)."""
    return Decomposition("pencil", tuple(axes))


def poisson_solve(rhs: jax.Array, plan: Croft3D, box: float = 2 * math.pi):
    """Spectral Poisson solve  ∇²u = f  on a periodic box (example app).

    Works with both problem classes: a c2c plan sees the full spectrum, an
    r2c plan the Hermitian half (kz from ``rfftfreq``) — the real path
    demonstrates the packed pipeline's halved round trip.  The 1/(-k²)
    multiplier is *fused* into the forward transform as a schedule
    epilogue (``plan.forward_filtered``): one dispatch, no separate pass
    over the spectrum.
    """
    nx, ny, nz = plan.shape
    kx = jnp.fft.fftfreq(nx, d=box / (2 * math.pi * nx))
    ky = jnp.fft.fftfreq(ny, d=box / (2 * math.pi * ny))
    if plan.problem == "r2c":
        kz = jnp.fft.rfftfreq(nz, d=box / (2 * math.pi * nz))
    else:
        kz = jnp.fft.fftfreq(nz, d=box / (2 * math.pi * nz))
    k2 = (kx[:, None, None] ** 2 + ky[None, :, None] ** 2
          + kz[None, None, :] ** 2)
    inv_k2 = jnp.where(k2 == 0, 0.0, -1.0 / jnp.where(k2 == 0, 1.0, k2))
    inv_k2 = inv_k2.astype(plan.dtype)
    if plan.mesh is not None:
        inv_k2 = jax.device_put(inv_k2, NamedSharding(
            plan.mesh, plan.output_sharding.spec))
    u_hat = plan.forward_filtered(rhs.astype(plan.input_dtype), inv_k2)
    return plan.inverse(u_hat)
