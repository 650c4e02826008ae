"""Per-stage traced execution: re-drive a plan's schedule with timing shims.

Nothing can be timed *inside* ``jit`` (XLA fuses and reorders; a timer
in the traced body would change the compiled HLO — the zero-cost
guarantee this subsystem pins in tests).  So attribution works by
re-driving the plan's :class:`~repro.core.schedule.Schedule` stage by
stage OUTSIDE the production jit: each stage becomes its own
``jit(shard_map(run_stage))`` whose in/out specs come from the
schedule's symbolic layouts, and the host clocks each dispatch +
``block_until_ready``.  For stages with a collective, the compute leg
(:func:`~repro.core.schedule.stage_pre`) and the collective leg
(:func:`~repro.core.schedule.stage_comm`) are additionally compiled
per K-chunk, so the serialized leg times F (fft) and C (collective)
are real measurements, not model splits.

The **measured overlap efficiency** of a comm stage then falls out of
three wall clocks: with F = serialized compute leg, C = serialized
collective leg, and W = the pipelined full stage,

    hidden = clamp(F + C - W, 0, C)        efficiency = hidden / C

i.e. the fraction of collective time that did NOT extend the stage's
critical path — the per-stage measured form of the paper's 42-51%
hiding claim, joined against ``tuning.cost_model.per_stage_costs``'s
predicted split by ``python -m repro.obs.report``.

Scope: c2c plans on a mesh (the packed real pipeline's stages carry
``den`` factors whose chunk shapes this re-driver does not reproduce;
r2c plans fall back to a single end-to-end span).
"""

from __future__ import annotations

import statistics
import time
from typing import Optional

import jax
from jax.sharding import NamedSharding

from jax import shard_map
from repro.core import schedule as schedule_lib
from repro.launch import hlo_cost
from repro.obs import tracer as tracer_lib


def _timed(tracer, exe, args, name, cat, iters, span_args):
    """Median wall time of ``exe(*args)`` over ``iters`` timed runs (one
    untimed warmup), one span per run; returns (median_s, last_output)."""
    out = exe(*args)
    jax.block_until_ready(out)
    times = []
    for n in range(iters):
        t0 = time.monotonic()
        out = exe(*args)
        jax.block_until_ready(out)
        t1 = time.monotonic()
        times.append(t1 - t0)
        tracer.complete(name, cat, t0, t1, dict(span_args, iter=n))
    return statistics.median(times), out


def _compile(tracer, fn, sds, name):
    with tracer.span(f"compile:{name}", "plan"):
        return jax.jit(fn).lower(sds).compile()


def _sds(mesh, shape, dtype, layout):
    return jax.ShapeDtypeStruct(
        shape, dtype, sharding=NamedSharding(mesh, layout.partition_spec()))


def trace_forward(plan, x, tracer=None, iters: int = 3,
                  label: Optional[str] = None) -> tuple:
    """Run ``plan.forward(x)`` with per-stage/per-chunk attribution.

    Emits spans into ``tracer`` (the process tracer by default), returns
    ``(y, summary)`` where ``y`` is the production ``plan.forward``
    output and ``summary`` the per-stage model-vs-measured rows
    (also attached to the trace metadata under ``"attribution"`` for
    ``repro.obs.report``).  ``x`` must be placed with
    ``plan.input_sharding``.
    """
    if tracer is None:
        tracer = tracer_lib.get_tracer()
    # plan.candidate() — not a hand-built Candidate — so searched
    # schedules attribute under their own pipeline identity/model rows
    cand = plan.candidate() if plan.decomp is not None else None
    label = label or (cand.label if cand is not None else "meshless")

    with tracer.span("e2e", "plan", plan=label):
        t0 = time.monotonic()
        y = plan.forward(x)
        jax.block_until_ready(y)
        e2e_s = time.monotonic() - t0

    summary = {
        "plan": label,
        "plan_key": cand.plan_key if cand is not None else None,
        "shape": list(plan.shape),
        "transpose_impl": plan.opts.transpose_impl,
        "overlap_k": plan.opts.overlap_k,
        "e2e_s": e2e_s,
        "stages": [],
        "overall": None,
    }
    if plan.mesh is None or plan.problem != "c2c":
        summary["note"] = ("per-stage attribution covers c2c mesh plans; "
                           "only the e2e span was recorded")
        _attach(tracer, summary)
        return y, summary

    mesh = plan.mesh
    opts = plan.opts
    axis_sizes = dict(mesh.shape)
    sched = plan._forward_schedule()
    from repro.tuning.cost_model import per_stage_costs
    model_rows = {r["stage"]: r for r in per_stage_costs(
        plan.shape, cand, axis_sizes, plan.dtype)}
    k_effs = dict(zip((i for i, _ in sched.comm_stages()),
                      sched.effective_k(plan.shape, axis_sizes,
                                        opts.overlap_k)))

    cur = x.astype(plan.dtype)
    total_c = total_hidden = 0.0
    for i, (st, pts) in enumerate(zip(sched.stages, sched.points)):
        cat = schedule_lib.stage_category(st)
        in_sds = _sds(mesh, cur.shape, plan.dtype, pts.entry)

        def full(blk, st=st):
            return schedule_lib.run_stage(blk, st, sched.sign, opts)

        exe = _compile(
            tracer, shard_map(full, mesh=mesh,
                              in_specs=pts.entry.partition_spec(),
                              out_specs=pts.out.partition_spec()),
            in_sds, f"s{i}:{st.name}")
        hlo = hlo_cost.summarize(hlo_cost.analyze_compiled(exe))

        row = dict(stage=i, name=st.name, category=cat,
                   k_eff=k_effs.get(i, 1), model=model_rows.get(i),
                   hlo=hlo)
        span_args = {"stage": i, "plan": label, "part": "stage",
                     "k_eff": row["k_eff"], **hlo}
        wall, out = _timed(tracer, exe, (cur,), f"s{i}:{st.name}", cat,
                           iters, span_args)
        row["wall_s"] = wall

        if st.comm_axis is not None:
            fft_s, comm_s, rounds = _split_legs(
                tracer, plan, sched, i, st, pts, cur, row["k_eff"], iters,
                label)
            hidden = min(max(fft_s + comm_s - wall, 0.0), comm_s)
            row.update(fft_s=fft_s, comm_s=comm_s, hidden_s=hidden,
                       measured_efficiency=(hidden / comm_s if comm_s
                                            else None))
            if rounds:
                row["rounds"] = rounds
            total_c += comm_s
            total_hidden += hidden
        else:
            row.update(fft_s=wall, comm_s=0.0, hidden_s=0.0,
                       measured_efficiency=None)
        summary["stages"].append(row)
        cur = out

    if total_c:
        summary["overall"] = {"collective_s": total_c,
                              "hidden_s": total_hidden,
                              "efficiency": total_hidden / total_c}
    _attach(tracer, summary)
    return y, summary


def _split_legs(tracer, plan, sched, i, st, pts, cur, k, iters, label):
    """Serialized compute/collective leg times of comm stage ``i``:
    per-K-chunk executables for :func:`stage_pre` / :func:`stage_comm`
    (chunking is local, exactly as the executor slices), summed over
    chunks.  For ring/pairwise stages the collective leg is additionally
    split into its P-1 ppermute rounds (:func:`schedule.ring_round`,
    chunk 0 only), so the trace shows where inside the ring the stage's
    wall time goes; returns ``(fft_s, comm_s, rounds)``."""
    mesh, opts = plan.mesh, plan.opts
    axis_sizes = dict(mesh.shape)
    ax = st.chunk_axis
    ext = pts.entry.local_shape(plan.shape, axis_sizes)[ax]
    ck = ext // k
    in_sds = _sds(mesh, cur.shape, plan.dtype, pts.entry)
    chunk_shape = list(cur.shape)
    chunk_shape[ax] = cur.shape[ax] // k

    fft_s = comm_s = 0.0
    rounds = []
    for j in range(k):
        def pre_j(blk, st=st, j=j):
            c = jax.lax.slice_in_dim(blk, j * ck, (j + 1) * ck, axis=ax)
            return schedule_lib.stage_pre(c, st, sched.sign, opts)

        exe_pre = _compile(
            tracer, shard_map(pre_j, mesh=mesh,
                              in_specs=pts.entry.partition_spec(),
                              out_specs=pts.comm.partition_spec()),
            in_sds, f"s{i}:{st.name}:fft[{j}]")
        dt, pre_out = _timed(
            tracer, exe_pre, (cur,), f"s{i}:{st.name}:fft", "fft", iters,
            {"stage": i, "plan": label, "part": "fft", "chunk": j, "k": k})
        fft_s += dt

        def comm_j(blk, st=st):
            return schedule_lib.stage_comm(blk, st, opts)

        exe_comm = _compile(
            tracer, shard_map(comm_j, mesh=mesh,
                              in_specs=pts.comm.partition_spec(),
                              out_specs=pts.out.partition_spec()),
            _sds(mesh, tuple(chunk_shape), plan.dtype, pts.comm),
            f"s{i}:{st.name}:comm[{j}]")
        dt, _ = _timed(
            tracer, exe_comm, (pre_out,), f"s{i}:{st.name}:comm",
            "collective", iters,
            {"stage": i, "plan": label, "part": "comm", "chunk": j, "k": k})
        comm_s += dt

        impl = schedule_lib.stage_transpose_impl(st, opts)
        p = 1
        for n in schedule_lib._flat(st.comm_axis):
            p *= axis_sizes[n]
        if j == 0 and impl in ("ring", "pairwise") and p > 1:
            for rnd in range(1, p):
                def round_r(blk, st=st, rnd=rnd):
                    return schedule_lib.ring_round(blk, st, opts, rnd)

                exe_round = _compile(
                    tracer, shard_map(round_r, mesh=mesh,
                                      in_specs=pts.comm.partition_spec(),
                                      out_specs=pts.comm.partition_spec()),
                    _sds(mesh, tuple(chunk_shape), plan.dtype, pts.comm),
                    f"s{i}:{st.name}:round[{rnd}]")
                rdt, _ = _timed(
                    tracer, exe_round, (pre_out,),
                    f"s{i}:{st.name}:round[{rnd}]", "collective", iters,
                    {"stage": i, "plan": label, "part": "round",
                     "round": rnd, "p": p})
                rounds.append({"round": rnd, "wall_s": rdt})
    return fft_s, comm_s, rounds


def _attach(tracer, summary) -> None:
    if not tracer.enabled:
        return
    attrib = tracer.meta().get("attribution", [])
    tracer.add_meta("attribution", attrib + [summary])
