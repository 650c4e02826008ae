"""Analytic candidate scoring — the planner's FFTW-``ESTIMATE`` leg.

Scores a :class:`~repro.tuning.candidates.Candidate` in modeled seconds
with zero execution.  Since the stage-schedule refactor the model does
not re-derive pipeline structure from ``Decomposition.kind``: it builds
the candidate's *actual* :class:`repro.core.schedule.Schedule` (the same
object the executor runs) and walks it —

  compute     5 n log2 n FLOPs per local FFT event, at the block size the
              schedule's symbolic layout reports for that stage, scaled
              by a per-``local_impl`` efficiency prior (the four-step
              matmul runs on the MXU, Stockham/XLA on the vector units)
  memory      ~10 local HBM passes over the per-device input block
  collective  per-stage transpose bytes (the layout at each stage's
              all_to_all, so the packed pipeline's half-volume stages and
              its out-of-body z-localizing reshard are charged at their
              true sizes) / link bandwidth
  latency     a per-collective launch cost using each stage's *effective*
              K (the executor's chunk-indivisible fallback is modeled,
              and out-of-body reshards count as one fused all-to-all);
              the alpha/beta split per transpose impl: "alltoall" pays
              one alpha per (chunk, stage) and its beta overlaps only
              when K >= 2 chunks exist to pipeline; "ring" pays P-1
              alphas per chunk plus one fused pack/unpack HBM pass each
              side, but its beta is overlapped with FFT compute even at
              K=1 (the rounds are independent of each other and of the
              neighbouring chunks' FFTs — the executor's explicit
              pipeline); "pairwise" pays P-1 alphas AND a serial
              placement chain (P-1 full-size output rewrites, never
              overlapped) — the FFTW3 baseline of figs 12-15

K-chunked overlap (the paper's core mechanism) combines compute and
collective with ``max(...)`` instead of ``+`` (§5.1 options 3/4), and
``plan_cache=False`` pays the twiddle re-materialization the paper's
options 1/3 measure.  The embedding r2c strategy additionally pays the
guarded half-slice reshard in the natural layout
(``core.rfft._guarded_half_slice``).

``batch`` models vmapped transforms (B stacked fields): volume terms
scale by B while collective launch counts do not — under vmap the
all_to_alls batch into the same ops — which is exactly what makes deeper
plans win at batch and why the wisdom key carries a ``|b{B}`` dimension.

For compiled refinement, :func:`hlo_collectives` extracts the *actual*
collective op count/bytes from post-SPMD HLO via ``launch/hlo_cost.py`` —
still execution-free, but it needs the mesh's devices to exist.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Mapping, Optional, Sequence

import jax.numpy as jnp

from repro.core.decomposition import Decomposition
from repro.core.distributed import FFTOptions, build_schedule
from repro.core.schedule import Schedule
from repro.launch.roofline import DEVICE_PEAKS, TARGET_DEVICE_KIND
from repro.tuning.candidates import Candidate

# Ranking priors.  The planner ranks candidates against one chip's
# peaks whatever it runs on: v5e's published FLOP/s, HBM bandwidth and
# per-link ICI bandwidth.  Chip traces calibrate them (ROADMAP queue 1,
# item 6); nothing reports a share of these.
PRIOR_PEAK_FLOPS = DEVICE_PEAKS[TARGET_DEVICE_KIND].flops
PRIOR_HBM_BW = DEVICE_PEAKS[TARGET_DEVICE_KIND].hbm_bw
PRIOR_LINK_BW = DEVICE_PEAKS[TARGET_DEVICE_KIND].link_bw

# fraction of peak FLOPs each local 1-D implementation is expected to
# sustain — coarse priors that mode="measure" refines empirically
IMPL_EFFICIENCY = {
    "matmul": 0.50,    # four-step DFT-by-matmul: MXU-native, extra flops
    "pallas": 0.40,    # same algorithm, hand-tiled kernel
    "stockham": 0.06,  # radix-2 butterflies on the vector units
    "xla": 0.08,       # backend-provided FFT custom call
}
_DEFAULT_EFFICIENCY = 0.08
LOCAL_PASSES = 10          # HBM round trips over the local block
COLLECTIVE_LATENCY_S = 2e-6  # alpha: seconds per collective launch
REPLAN_PASSES = 6          # twiddle re-materialization, options 1/3


@dataclasses.dataclass(frozen=True)
class CostBreakdown:
    """Modeled wall-clock terms for one candidate (seconds)."""

    compute_s: float
    memory_s: float
    collective_s: float
    latency_s: float
    replan_s: float
    total_s: float
    flops: float
    local_bytes: float
    collective_bytes: float
    n_collectives: int
    n_procs: int
    #: ring pack/unpack passes or the pairwise serial placement chain
    transpose_overhead_s: float = 0.0

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


def flops_model(shape: Sequence[int]) -> float:
    """Analytic 5 N log2 N FLOPs of the full c2c 3-D transform."""
    n_total = math.prod(shape)
    return 5.0 * n_total * sum(math.log2(s) for s in shape)


def schedule_for(shape: Sequence[int], cand: Candidate) -> Schedule:
    """The forward schedule this candidate would execute — the single
    source of stage structure for both the executor and this model
    (``Croft3D._forward_schedule`` reads it too).

    The r2c embedding's guarded half-slice (``core.rfft``, natural
    layout only: the odd-sized Nh axis is resharded z-local before
    slicing) is recorded as an out-of-body ``ExtraComm`` of ~half the
    spectrum volume, so its bytes and launch are charged like any other
    collective.
    """
    from repro.tuning.candidates import split_grad
    build = getattr(cand, "build_schedule", None)
    if build is not None:
        # searched pipeline: the candidate IS the schedule (stage list +
        # per-stage overrides); nothing to re-derive from the builders
        return build()
    base_problem, _ = split_grad(cand.problem)
    if base_problem == "r2c" and cand.strategy == "packed":
        from repro.real import pipeline as real_pipeline
        return real_pipeline.build_packed_forward(cand.decomp)
    sched = build_schedule(cand.decomp, cand.opts, sign=-1)
    if (base_problem == "r2c" and cand.strategy == "embed"
            and cand.opts.output_layout == "natural"):
        from repro.core.schedule import ExtraComm
        half = sched.layout_out.with_den(2, mul=2)
        sched = dataclasses.replace(
            sched, extra_comms=sched.extra_comms
            + (ExtraComm("guarded-half-slice", half),))
    return sched


def schedules_for(shape: Sequence[int], cand: Candidate) -> list:
    """Every schedule one step of this candidate executes: the forward,
    plus its adjoint (``repro.grad``) for the ``_grad`` problems — the
    training-step cost is their sum, and the adjoint's stage structure
    (same transposes, mirrored order) is priced with the same model."""
    from repro.tuning.candidates import split_grad
    sched = schedule_for(shape, cand)
    _, is_grad = split_grad(cand.problem)
    if not is_grad:
        return [sched]
    from repro.grad import adjoint_schedule
    return [sched, adjoint_schedule(sched)]


def analytic_cost(shape: Sequence[int], cand: Candidate,
                  axis_sizes: Mapping[str, int],
                  dtype=jnp.complex64, batch: int = 1) -> CostBreakdown:
    """Modeled seconds for one execution of this candidate — one forward
    transform, or one fwd+bwd pair for the ``_grad`` problems (the
    schedules run sequentially, so their modeled times sum)."""
    parts = [_schedule_cost(shape, cand, sched, axis_sizes, dtype, batch)
             for sched in schedules_for(shape, cand)]
    if len(parts) == 1:
        return parts[0]
    return CostBreakdown(**{
        f.name: (sum(getattr(b, f.name) for b in parts)
                 if f.name != "n_procs" else parts[0].n_procs)
        for f in dataclasses.fields(CostBreakdown)})


def _schedule_cost(shape: Sequence[int], cand: Candidate, sched: Schedule,
                   axis_sizes: Mapping[str, int],
                   dtype=jnp.complex64, batch: int = 1) -> CostBreakdown:
    if getattr(cand, "is_schedule", False):
        return _searched_schedule_cost(shape, cand, sched, axis_sizes,
                                       dtype, batch)
    decomp, opts = cand.decomp, cand.opts
    itemsize = jnp.dtype(dtype).itemsize
    p = decomp.n_procs(axis_sizes)
    alpha, beta = COLLECTIVE_LATENCY_S, 1.0 / PRIOR_LINK_BW

    # compute: one event per local FFT, at the schedule's reported size
    flops = 0.0
    compute_s = 0.0
    for impl_stage, elems, n_fft in sched.fft_events(shape, axis_sizes):
        f = 5.0 * elems * math.log2(n_fft)
        flops += f
        eff = IMPL_EFFICIENCY.get(opts.stage_impl(impl_stage),
                                  _DEFAULT_EFFICIENCY)
        compute_s += f / (PRIOR_PEAK_FLOPS * eff)
    flops *= batch
    compute_s *= batch

    local_bytes = sched.layout_in.bytes(shape, axis_sizes, itemsize) * batch
    memory_s = LOCAL_PASSES * local_bytes / PRIOR_HBM_BW

    events = sched.comm_events(shape, axis_sizes, itemsize)
    coll_bytes = float(sum(ev["bytes"] for ev in events)) * batch
    collective_s = coll_bytes * beta

    # collective-op count: effective K chunks per in-body transpose (the
    # executor's chunk-indivisible fallback, read from the schedule); the
    # ppermute-based transposes (ring, pairwise) issue (P_axis - 1)
    # rounds where the fused path issues one a2a; out-of-body reshards
    # are one fused a2a each.  Alongside the alpha count, each impl's
    # structural overhead: the ring pays one fused pack + one fused
    # unpack pass over the moved bytes, the pairwise emulation pays a
    # *serial* placement chain of P-1 full-size output rewrites.
    impl = opts.transpose_impl
    eff_ks = iter(sched.effective_k(shape, axis_sizes, opts.overlap_k))
    n_coll = 0
    k_eff_max = 1
    any_chunkable = False
    transpose_overhead_s = 0.0
    for ev in events:
        if not ev["chunkable"]:
            n_coll += 1
            continue
        any_chunkable = True
        k_eff = next(eff_ks)
        k_eff_max = max(k_eff_max, k_eff)
        ops = (ev["comm_size"] - 1) if impl in ("ring", "pairwise") else 1
        n_coll += k_eff * ops
        ev_bytes = ev["bytes"] * batch
        if impl == "ring":
            transpose_overhead_s += 2 * ev_bytes / PRIOR_HBM_BW
        elif impl == "pairwise":
            transpose_overhead_s += ((ev["comm_size"] - 1) * ev_bytes
                                     / PRIOR_HBM_BW)
    latency_s = n_coll * alpha

    replan_s = 0.0
    if not opts.plan_cache:
        replan_s = REPLAN_PASSES * local_bytes / PRIOR_HBM_BW

    busy = compute_s + memory_s
    if impl == "ring":
        busy += transpose_overhead_s  # pack/unpack pipeline with the rounds
    # beta overlap: K >= 2 chunks pipeline any impl's collective against
    # the neighbouring chunks' FFTs; the ring's independent rounds
    # additionally overlap at K=1.  The pairwise serial chain never
    # overlaps — each round's placement depends on the previous one.
    overlaps = (any_chunkable and impl != "pairwise"
                and (k_eff_max >= 2 or impl == "ring"))
    if overlaps:
        # paper §5.1: chunked pipeline hides the smaller of the two legs
        overlapped = max(busy, collective_s) + 0.1 * min(busy, collective_s)
    else:
        overlapped = busy + collective_s
        if impl == "pairwise":
            overlapped += transpose_overhead_s
    total = overlapped + latency_s + replan_s

    return CostBreakdown(
        compute_s=compute_s, memory_s=memory_s, collective_s=collective_s,
        latency_s=latency_s, replan_s=replan_s, total_s=total, flops=flops,
        local_bytes=float(local_bytes), collective_bytes=float(coll_bytes),
        n_collectives=n_coll, n_procs=p,
        transpose_overhead_s=transpose_overhead_s)


def _searched_schedule_cost(shape: Sequence[int], cand, sched: Schedule,
                            axis_sizes: Mapping[str, int],
                            dtype=jnp.complex64,
                            batch: int = 1) -> CostBreakdown:
    """Per-stage §5.1 combine for searched pipelines.

    The legacy formula prices the whole schedule with one global
    ``max(busy, collective)`` — fine for homogeneous knobs, but it can
    hide a stage that *cannot* overlap (chunk-indivisible alltoall)
    under another stage's compute, which is not physical.  Searched
    schedules mix impls and K per stage, so each stage's overlap is
    priced against its OWN legs — the same decomposition
    :func:`per_stage_costs` reports — and the stage times sum.
    Fixed-builder candidates keep the legacy combine so existing
    rankings and pins are bit-identical.
    """
    from repro.core.schedule import _flat, stage_transpose_impl
    opts = cand.opts
    itemsize = jnp.dtype(dtype).itemsize
    p = cand.decomp.n_procs(axis_sizes)
    alpha, beta = COLLECTIVE_LATENCY_S, 1.0 / PRIOR_LINK_BW

    flops = 0.0
    compute_s = 0.0
    for impl_stage, elems, n_fft in sched.fft_events(shape, axis_sizes):
        f = 5.0 * elems * math.log2(n_fft)
        flops += f
        eff = IMPL_EFFICIENCY.get(opts.stage_impl(impl_stage),
                                  _DEFAULT_EFFICIENCY)
        compute_s += f / (PRIOR_PEAK_FLOPS * eff)
    flops *= batch
    compute_s *= batch

    local_bytes = sched.layout_in.bytes(shape, axis_sizes, itemsize) * batch
    memory_s = LOCAL_PASSES * local_bytes / PRIOR_HBM_BW

    events = sched.comm_events(shape, axis_sizes, itemsize)
    coll_bytes = float(sum(ev["bytes"] for ev in events)) * batch
    collective_s = coll_bytes * beta

    eff_ks = iter(sched.effective_k(shape, axis_sizes, opts.overlap_k))
    comm_stages = iter(sched.comm_stages())
    n_coll = 0
    transpose_overhead_s = 0.0
    for ev in events:
        if not ev["chunkable"]:
            n_coll += 1
            continue
        _, st = next(comm_stages)
        impl = stage_transpose_impl(st, opts)
        k_eff = next(eff_ks)
        ops = (ev["comm_size"] - 1) if impl in ("ring", "pairwise") else 1
        n_coll += k_eff * ops
        ev_bytes = ev["bytes"] * batch
        if impl == "ring":
            transpose_overhead_s += 2 * ev_bytes / PRIOR_HBM_BW
        elif impl == "pairwise":
            transpose_overhead_s += ((ev["comm_size"] - 1) * ev_bytes
                                     / PRIOR_HBM_BW)
    latency_s = n_coll * alpha

    replan_s = 0.0
    if not opts.plan_cache:
        replan_s = REPLAN_PASSES * local_bytes / PRIOR_HBM_BW

    # the per-stage combine: each stage hides the smaller of its own two
    # legs when it pipelines (ring overhead is already inside the rows'
    # compute leg; the pairwise chain rides in compute and never hides)
    rows = _stage_rows(shape, cand, sched, axis_sizes, dtype, batch, "fwd")
    staged = 0.0
    for r in rows:
        c, coll = r["compute_s"], r["collective_s"]
        if r["overlaps"]:
            staged += max(c, coll) + 0.1 * min(c, coll)
        else:
            staged += c + coll
    total = staged + latency_s + replan_s

    return CostBreakdown(
        compute_s=compute_s, memory_s=memory_s, collective_s=collective_s,
        latency_s=latency_s, replan_s=replan_s, total_s=total, flops=flops,
        local_bytes=float(local_bytes), collective_bytes=float(coll_bytes),
        n_collectives=n_coll, n_procs=p,
        transpose_overhead_s=transpose_overhead_s)


def predicted_collectives(sched: Schedule, shape: Sequence[int],
                          axis_sizes: Mapping[str, int], opts) -> dict:
    """Per-kind collective-op counts the executor will emit for this
    schedule — what ``tests/test_schedule_search.py`` pins compiled HLO
    against: one ``all-to-all`` per effective chunk of a fused stage,
    ``K_eff * (P-1)`` ``collective-permute`` rounds for ring/pairwise,
    one fused all-to-all per out-of-body reshard."""
    from repro.core.schedule import _flat, stage_transpose_impl
    sizes = dict(axis_sizes)
    counts = {"all-to-all": 0, "collective-permute": 0}
    eff = sched.effective_k(shape, axis_sizes, opts.overlap_k)
    for (_, st), k_eff in zip(sched.comm_stages(), eff):
        impl = stage_transpose_impl(st, opts)
        csize = math.prod(sizes[n] for n in _flat(st.comm_axis))
        if impl == "alltoall":
            counts["all-to-all"] += k_eff
        else:
            counts["collective-permute"] += k_eff * (csize - 1)
    counts["all-to-all"] += len(sched.extra_comms)
    return counts


def per_stage_costs(shape: Sequence[int], cand: Candidate,
                    axis_sizes: Mapping[str, int],
                    dtype=jnp.complex64, batch: int = 1) -> list:
    """Modeled per-stage compute/collective split, the stage by stage
    counterpart of a chip trace's ``croft.stage.*`` device time.

    One row per schedule stage (plus one per out-of-body reshard), using
    the same conventions as :func:`analytic_cost`: FFT flops at the
    layout-reported block size over ``PRIOR_PEAK_FLOPS * IMPL_EFFICIENCY``,
    the ``LOCAL_PASSES`` HBM budget spread evenly across the stages that
    do local work, ring pack/unpack passes charged to the compute leg,
    and the §5.1 overlap rule (0.9 of the smaller leg hides under the
    larger when the stage pipelines: any chunkable stage with effective
    K >= 2, or the ring's independent rounds even at K=1; the pairwise
    serial chain never overlaps).  ``predicted_efficiency`` is the
    modeled fraction of the stage's collective time hidden under
    compute — the per-stage form of the paper's 42-51% claim.
    """
    rows = []
    scheds = schedules_for(shape, cand)
    for direction, sched in zip(("fwd", "bwd"), scheds):
        rows.extend(_stage_rows(shape, cand, sched, axis_sizes, dtype,
                                batch, direction))
    return rows


def _stage_rows(shape, cand, sched, axis_sizes, dtype, batch,
                direction) -> list:
    opts = cand.opts
    itemsize = jnp.dtype(dtype).itemsize
    beta = 1.0 / PRIOR_LINK_BW
    eff_ks = iter(sched.effective_k(shape, axis_sizes, opts.overlap_k))

    from repro.core.schedule import (_flat, stage_category,
                                     stage_transpose_impl)
    n_local = sum(1 for st in sched.stages
                  if st.fft_axis is not None or st.prologue or st.epilogue)
    mem_passes = LOCAL_PASSES / max(1, n_local)

    rows = []
    for i, (st, pts) in enumerate(zip(sched.stages, sched.points)):
        compute_s = 0.0
        if st.fft_axis is not None:
            loc = pts.fft.local_shape(shape, axis_sizes)
            f = 5.0 * math.prod(loc) * math.log2(loc[st.fft_axis])
            eff = IMPL_EFFICIENCY.get(opts.stage_impl(st.impl_stage),
                                      _DEFAULT_EFFICIENCY)
            compute_s += f / (PRIOR_PEAK_FLOPS * eff)
        if st.fft_axis is not None or st.prologue or st.epilogue:
            compute_s += (mem_passes
                          * pts.entry.bytes(shape, axis_sizes, itemsize)
                          / PRIOR_HBM_BW)
        compute_s *= batch

        collective_s = 0.0
        k_eff = 1
        overlaps = False
        if st.comm_axis is not None:
            impl = stage_transpose_impl(st, opts)
            ev_bytes = pts.comm.bytes(shape, axis_sizes, itemsize) * batch
            collective_s = ev_bytes * beta
            k_eff = next(eff_ks)
            overlaps = impl != "pairwise" and (k_eff >= 2 or impl == "ring")
            if impl == "ring":
                compute_s += 2 * ev_bytes / PRIOR_HBM_BW
            elif impl == "pairwise":
                csize = math.prod(axis_sizes[n] for n in _flat(st.comm_axis))
                compute_s += (csize - 1) * ev_bytes / PRIOR_HBM_BW

        hidden = 0.9 * min(compute_s, collective_s) if overlaps else 0.0
        rows.append({
            "stage": i,
            "name": st.name,
            "direction": direction,
            "category": stage_category(st),
            "impl": (stage_transpose_impl(st, opts)
                     if st.comm_axis is not None else None),
            "compute_s": compute_s,
            "collective_s": collective_s,
            "k_eff": k_eff,
            "overlaps": overlaps,
            "hidden_s": hidden,
            "predicted_efficiency": (hidden / collective_s
                                     if collective_s else None),
        })
    for ec in sched.extra_comms:
        coll = ec.layout.bytes(shape, axis_sizes, itemsize) * batch * beta
        rows.append({
            "stage": None, "name": ec.name, "direction": direction,
            "category": "collective",
            "compute_s": 0.0, "collective_s": coll, "k_eff": 1,
            "overlaps": False, "hidden_s": 0.0,
            "predicted_efficiency": 0.0 if coll else None,
        })
    return rows


def rank_candidates(shape: Sequence[int], cands: Sequence[Candidate],
                    axis_sizes: Mapping[str, int],
                    dtype=jnp.complex64,
                    batch: int = 1) -> list[tuple[Candidate, CostBreakdown]]:
    """Candidates sorted by modeled total time, cheapest first (stable —
    enumeration order breaks ties, keeping ranking deterministic)."""
    scored = [(c, analytic_cost(shape, c, axis_sizes, dtype, batch))
              for c in cands]
    scored.sort(key=lambda t: t[1].total_s)
    return scored


def hlo_collectives(plan) -> Optional[dict]:
    """Collective counts/bytes of the compiled forward, from post-SPMD HLO
    (``launch/hlo_cost.py``).  Compiles but never executes; returns None
    when lowering is impossible (e.g. the mesh's devices don't exist in
    this process)."""
    from repro.launch import hlo_cost
    try:
        compiled = plan.lower_forward().compile()
        cost = hlo_cost.analyze(compiled.as_text())
    except Exception:
        return None
    return {
        "collective_bytes": cost.collective_bytes,
        "collectives": cost.collectives,
        "flops": cost.flops,
        "bytes": cost.bytes,
    }
