#!/usr/bin/env python3
"""Split a ``jax.profiler`` trace by the names the program puts on it.

``bench/trace_reduce.py`` sorts device ops by HLO opcode and names idle
gaps by the benchmark's own ``bench.*`` host spans.  This module reads
what the program names itself (``repro.obs.scopes`` in ``src``):

- each device op's scope path, whose innermost ``croft.<role>``
  component is its role (``croft.dft``, ``croft.relayout``,
  ``croft.transpose``, ``croft.scale``), whose ``croft.stage.<name>``
  component is its schedule stage and whose ``k<i>`` component, right
  after the stage, is its chunk of a K-chunked stage;
- the program run each op belongs to (module name and run id);
- the program's ``croft.*`` host spans, kept apart from the ``bench.*``
  ones so that ``trace_reduce.SpanIndex`` names gaps as before.

Where these come from.  On a TPU v5e under JAX 0.9 a device op event
holds its HLO text as its name and no ``op_name`` stat (its stats are
``device_offset_ps``, ``device_duration_ps`` and ``Time Scale
Multiplier``), and the ``/host:metadata`` plane is empty.  So an op's
program is the ``XLA Modules`` event of its plane that contains it
(``jit_croft_forward(<fingerprint>)``, with a ``run_id`` stat), and its
scope is the ``op_name`` of its instruction in that program's compiled
HLO text, which the caller passes in (``Croft3D.lower(entry).compile()
.as_text()``).  Each op is checked against that text: its instruction
must be there, with the opcode of the op event, or the trace ran another
program and :class:`TraceMismatch` is raised.  On the CPU backend an op
event names its module (``hlo_module``) and run (``run_id``) itself.

What XLA adds on its own to move data carries no ``op_name`` of the
program (the split of a complex argument into its real and imaginary
planes, the combine at a ``shard_map``'s edge, layout copies, constant
prefetches).  The reader names it ``(xla)``, a key of its own beside
the four roles, under the stage of the nearest op of the data flow that
has one; it counts as time without a role, never as ``croft.relayout``.

:func:`reduce` gives, per device and over the window: device time per
role (the union of the role's op intervals, clipped to the window, like
``trace_reduce``'s categories), time without a role, and the idle gaps
split into those between two ops of one program run and those between
two runs.  The ops it reads are ``trace_reduce.Op`` with three more
fields, so ``trace_reduce.reduce`` reads the same ops as it reads today.

:func:`main` runs one cell on the chip, the window under the profiler
with the program's spans on its clock, and prints one JSON line: the
seven per-layer metrics of ``bench/metrics`` read from the same trace,
the four that this module adds (``dft_ms``, ``relayout_ms``,
``idle_in_program_ms``, ``idle_between_programs_ms``), ``unscoped_ms``
and ``xla_moves_ms``, and the breakdowns ``scopes``, ``programs`` and
``idle_boundaries``:

    python3 bench/trace_scopes.py --workload pme-128.step --seed 5 \\
        --seconds 10

It stands in for ``bench/harness.run_cell`` until the harness reads
these metrics itself (``_traced_window`` opening the span sink and
calling :func:`load_xplane` and :func:`reduce`); delete :func:`main`
then, and keep the readers.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import os
import re
import sys

if __name__ == "__main__":  # a script: find bench/ and src/ beside it
    _ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

from bench import trace_reduce  # noqa: E402
from bench.trace_reduce import Span, clip, total, union  # noqa: E402

ROLES = ("croft.dft", "croft.relayout", "croft.transpose", "croft.scale")
STAGE_PREFIX = "croft.stage."
CROFT_SPAN_PREFIX = "croft."
UNSCOPED = "(unscoped)"
MODULES_LINE = "XLA Modules"

XLA_TAG = "(xla)"
# what XLA adds on its own, with no op_name, to move data: layout copies,
# buffer moves, constant prefetches, the split and combine of 64-bit
# values into 32-bit halves; a fusion of these alone moves data too
MOVES = frozenset({
    "parameter", "constant", "bitcast", "copy", "copy-start", "copy-done",
    "transpose", "reshape", "broadcast", "slice", "dynamic-slice",
    "dynamic-update-slice", "concatenate", "pad", "reverse", "tuple",
    "get-tuple-element", "custom-call:AllocateBuffer",
    "custom-call:X64Combine", "custom-call:X64SplitLow",
    "custom-call:X64SplitHigh"})
_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')
_TARGET_RE = re.compile(r'custom_call_target="([^"]+)"')
_CALLS_RE = re.compile(r"calls=%([\w.\-]+)")
_REF_RE = re.compile(r"%([\w.\-]+)")
_RUN_RE = re.compile(r"\(\d+\)$")
_CHUNK_RE = re.compile(r"k\d+$")


class TraceMismatch(ValueError):
    """A traced op that its program's compiled text does not hold."""


@dataclasses.dataclass(frozen=True)
class ScopedOp(trace_reduce.Op):
    """A device op with what the program named: its ``op_name`` scope
    path ("" when unknown), its program (module name) and run id."""
    scope: str = ""
    program: str = ""
    run: object = None


def role_of(scope: str) -> str:
    """The role of an ``op_name`` scope path: its innermost role scope,
    ``(xla)`` for what the reader names XLA's own data movement
    (:func:`op_names`), or UNSCOPED.  XLA joins the paths of the ops it
    merges into one with ``;``; the first path that names a role gives
    it."""
    for path in scope.split(";"):
        for part in reversed(path.split("/")):
            if part in ROLES or part == XLA_TAG:
                return part
    return UNSCOPED


def _joined(scope: str, part_of) -> str:
    out = []
    for path in scope.split(";"):
        part = part_of(path.split("/"))
        if part and part not in out:
            out.append(part)
    return ";".join(out)


def _place(parts: list) -> str:
    for i, p in enumerate(parts):
        if p.startswith(STAGE_PREFIX):
            if i + 1 < len(parts) and _CHUNK_RE.match(parts[i + 1]):
                return f"{p}/{parts[i + 1]}"
            return p
    return ""


def place_of(scope: str) -> str:
    """The stage of an ``op_name`` path and, in a K-chunked stage, its
    chunk: ``croft.stage.<name>/k<i>``; merged paths joined with ``;``."""
    return _joined(scope, _place)


def scope_key(scope: str) -> str:
    """The breakdown's key of an op: stage, chunk and role
    (``croft.stage.x-fft+xy/k0/croft.transpose``), so that the chunks of
    a pipelined stage read apart."""
    role = role_of(scope)
    if role == UNSCOPED:
        return UNSCOPED
    place = place_of(scope)
    return f"{place}/{role}" if place else role


@dataclasses.dataclass
class _Instr:
    op: str             # opcode, with a custom call's target
    op_name: str
    refs: list          # operands, and computations it calls
    calls: str          # a fusion's computation
    comp: str           # the computation it sits in


def _parse_hlo(hlo_text: str) -> dict:
    """Instruction name -> _Instr of one compiled HLO module's text."""
    out, comp = {}, ""
    for raw in hlo_text.splitlines():
        if raw and not raw[0].isspace() and raw.rstrip().endswith("{"):
            words = raw.split()
            comp = (words[1] if words[0] == "ENTRY" else words[0]).lstrip("%")
            continue
        line = raw.strip()
        if line.startswith("ROOT "):
            line = line[5:]
        if not line.startswith("%") or " = " not in line:
            continue
        name, op = trace_reduce.parse_op(line)
        if name == line:
            continue
        target = _TARGET_RE.search(line)
        if target:
            op += ":" + target.group(1)
        op_name = _OP_NAME_RE.search(line)
        calls = _CALLS_RE.search(line)
        out[name] = _Instr(op, op_name.group(1) if op_name else "",
                           _REF_RE.findall(line.split(" = ", 1)[1]),
                           calls.group(1) if calls else "", comp)
    return out


def op_names(hlo_text: str) -> dict:
    """Instruction name -> scope path of one compiled HLO module's text:
    the instruction's own ``op_name`` where it names a role; else, for
    what only moves data (``MOVES``, or a fusion of nothing else: what
    XLA adds with no ``op_name``, and the split and combine of complex
    values at a program's or a ``shard_map``'s edges), ``(xla)`` under
    its stage or that of the nearest instruction in the data flow that
    has one.  Anything else without a role reads as unscoped."""
    return {name: scope for name, (_, scope) in _scoped(
        _parse_hlo(hlo_text)).items()}


def _scoped(instrs: dict) -> dict:
    """Instruction name -> (opcode, scope path) of :func:`op_names`."""
    by_comp = collections.defaultdict(list)
    users = collections.defaultdict(list)
    for name, ins in instrs.items():
        by_comp[ins.comp].append(ins)
        for ref in ins.refs:
            users[ref].append(name)

    def moves(ins) -> bool:
        if ins.op == "fusion":
            body = by_comp.get(ins.calls)
            return bool(body) and all(moves(i) for i in body)
        return ins.op in MOVES

    def place_near(name) -> str:
        """The stage and chunk of the nearest instruction in the data flow
        (its operands and users, breadth first) whose op_name has one."""
        seen, frontier = {name}, [name]
        while frontier:
            nxt = []
            for n in frontier:
                for m in instrs[n].refs + users[n]:
                    if m in instrs and m not in seen:
                        place = place_of(instrs[m].op_name)
                        if place:
                            return place
                        seen.add(m)
                        nxt.append(m)
            frontier = nxt
        return ""

    out = {}
    for name, ins in instrs.items():
        scope = ins.op_name
        if role_of(scope) == UNSCOPED and moves(ins):
            scope = "/".join(p for p in (
                place_of(scope) or place_near(name), XLA_TAG) if p)
        out[name] = (ins.op, scope)
    return out


def module_name(event_name: str) -> str:
    """``jit_croft_forward`` of an ``XLA Modules`` event name."""
    return _RUN_RE.sub("", event_name)


def load_xplane(path: str, hlo_texts=None):
    """(scoped device ops, benchmark spans, ``croft.*`` spans, program
    runs) of one ``.xplane.pb``.  The ops are those
    ``trace_reduce.load_xplane`` keeps, in the same order; the runs map
    a device to its sorted ``XLA Modules`` events (start, end, module,
    run id), none on the CPU.  ``hlo_texts`` maps a module name to its
    compiled HLO text, or is a function from the set of module names in
    the trace to such a map.  An op of a module given here whose
    instruction that text lacks, or holds with another opcode than the
    op event's HLO text (a TPU's events carry it; a CPU's name only the
    instruction), raises :class:`TraceMismatch`: the text is not that
    of the program that ran (:func:`attach`)."""
    from jax.profiler import ProfileData

    ops, spans = trace_reduce.load_xplane(path)
    data = ProfileData.from_file(path)
    runs: dict = {}           # device -> sorted [(start, end, module, run)]
    croft_spans = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name != MODULES_LINE:
                    continue
                evs = []
                for ev in line.events:
                    stats = dict(ev.stats)
                    evs.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                module_name(ev.name), stats.get("run_id")))
                runs[plane.name] = sorted(evs)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(CROFT_SPAN_PREFIX):
                        croft_spans.append(Span(
                            ev.name, ev.start_ns,
                            ev.start_ns + ev.duration_ns))
    cpu_runs = {} if runs else _cpu_runs(data)
    if callable(hlo_texts):
        hlo_texts = hlo_texts({r[2] for evs in runs.values() for r in evs}
                              | {m for m, _ in cpu_runs.values()})

    def where(op):
        return (cpu_runs.get((op.device, op.start))
                or _find_run(runs.get(op.device), op.start))
    # a TPU's op event holds its HLO text, opcode included; a CPU's names
    # only the instruction
    scoped = attach(ops, where, hlo_texts, opcodes=bool(runs))
    return scoped, spans, croft_spans, runs


def attach(ops, where, hlo_texts, opcodes: bool = True) -> list:
    """``ops`` as :class:`ScopedOp`: ``where(op)`` gives an op's (module,
    run), ``hlo_texts`` a module's compiled text, which gives the op's
    scope by its instruction's name.  Raises :class:`TraceMismatch` if
    an op of a module in ``hlo_texts`` has no instruction there or, with
    ``opcodes``, one of another opcode than its label's."""
    names = {mod: _scoped(_parse_hlo(text))
             for mod, text in (hlo_texts or {}).items()}
    scoped, wrong = [], collections.Counter()
    for op in ops:
        module, run = where(op)
        instr, _, opcode = op.name.partition(" ")
        scope = ""
        if module in names:
            known = names[module].get(instr)
            if known is None or (opcodes and opcode != known[0]):
                wrong[f"{module}:{op.name}"] += 1
            else:
                scope = known[1]
        scoped.append(ScopedOp(op.device, op.name, op.start, op.end,
                               op.category, scope, module, run))
    if wrong:
        raise TraceMismatch(
            f"{sum(wrong.values())} traced ops are not in their program's "
            f"compiled text, or have another opcode there: "
            f"{sorted(wrong)[:5]}")
    return scoped


def _find_run(evs, t: float):
    """(module, run) of the ``XLA Modules`` event that holds time t."""
    if not evs:
        return "", None
    i = bisect.bisect_right(evs, (t, float("inf"))) - 1
    if i >= 0 and evs[i][0] <= t < evs[i][1]:
        return evs[i][2], evs[i][3]
    return "", None


def _cpu_runs(data) -> dict:
    """(device, start) -> (module, run) of the CPU backend's op events,
    which name both in their stats."""
    out = {}
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                stats = dict(ev.stats)
                if "hlo_op" in stats and ev.duration_ns > 0:
                    dev = "cpu:%d" % int(stats.get("device_ordinal", 0))
                    out[(dev, ev.start_ns)] = (
                        str(stats.get("hlo_module", "")),
                        stats.get("run_id"))
    return out


# -- reduction --------------------------------------------------------------

@dataclasses.dataclass
class ScopeReduction:
    """What the program's names say about one window, per device."""
    base: object              # trace_reduce.Reduction of the same ops
    role_ns: dict             # device -> {role, XLA_TAG or UNSCOPED: ns}
    no_role_ns: dict          # device -> ns of ops the program gave no
    #                           role (XLA_TAG and UNSCOPED together)
    idle_in_program_ns: dict  # device -> ns
    idle_between_ns: dict     # device -> ns
    idle_edge_ns: dict        # device -> ns before the first/after the last op
    idle_between_in_module_ns: dict  # device -> the part of idle_between_ns
    #                           inside a program's XLA Modules interval
    scope_ns: dict            # scope_key -> ns, mean over devices
    program_ns: dict          # module -> ns, mean over devices
    idle_pairs: dict          # "a -> b" -> ns, mean over devices
    idle_in_by_role: dict     # role of the op before the gap -> ns, mean
    croft_spans: list         # the program's spans inside the window

    def mean(self, attr: str, key: str | None = None) -> float:
        vals = []
        for v in getattr(self, attr).values():
            vals.append(v.get(key, 0.0) if key is not None else v)
        return sum(vals) / len(vals) if vals else 0.0


def _unions(ops, key, lo, hi) -> dict:
    by = collections.defaultdict(list)
    for op in ops:
        by[key(op)].append((op.start, op.end))
    return {k: total(clip(union(iv), lo, hi)) for k, iv in by.items()}


def reduce(ops, spans, croft_spans=(), window=None,
           runs=None) -> ScopeReduction:
    """Reduce scoped device ops over ``window`` (the ``bench.window``
    span by default, as ``trace_reduce.reduce`` takes it).  ``runs``
    (from :func:`load_xplane`) tells, of the idle between two programs'
    ops, the part in which a program is still open on the device."""
    base = trace_reduce.reduce(ops, spans, window)
    lo, hi = base.window
    by_dev = collections.defaultdict(list)
    for op in ops:
        by_dev[op.device].append(op)
    role_ns, no_role, idle_in, idle_between, idle_edge, in_module = (
        {}, {}, {}, {}, {}, {})
    scope_acc: collections.Counter = collections.Counter()
    prog_acc: collections.Counter = collections.Counter()
    pair_acc: collections.Counter = collections.Counter()
    in_role_acc: collections.Counter = collections.Counter()
    for dev, dops in sorted(by_dev.items()):
        # relayout time counts what is not a collective (a relayout that
        # XLA's partitioner makes a collective is collective time); the
        # other roles count every op
        role_ns[dev] = _unions(
            [o for o in dops if not (role_of(o.scope) == "croft.relayout"
                                     and o.category == "collective")],
            lambda o: role_of(o.scope), lo, hi)
        no_role[dev] = _unions(
            dops, lambda o: role_of(o.scope) in (XLA_TAG, UNSCOPED),
            lo, hi).get(True, 0.0)
        scope_acc.update(_unions(dops, lambda o: scope_key(o.scope), lo, hi))
        prog_acc.update(_unions(dops, lambda o: o.program or UNSCOPED,
                                lo, hi))
        ins = betw = edge = opened = 0.0
        modules = union((r[0], r[1]) for r in (runs or {}).get(dev, ()))
        by_end = sorted(dops, key=lambda o: o.end)
        ends = [o.end for o in by_end]
        by_start = sorted(dops, key=lambda o: o.start)
        starts = [o.start for o in by_start]
        for s, e in base.devices[dev].gaps:
            i = bisect.bisect_right(ends, s) - 1
            j = bisect.bisect_left(starts, e)
            if i < 0 or j >= len(by_start):
                edge += e - s
                continue
            before, after = by_end[i], by_start[j]
            if before.program and (before.program, before.run) == \
                    (after.program, after.run):
                ins += e - s
                in_role_acc[role_of(before.scope)] += e - s
            else:
                betw += e - s
                opened += total(clip(modules, s, e))
                pair_acc[f"{before.program or UNSCOPED} -> "
                         f"{after.program or UNSCOPED}"] += e - s
        idle_in[dev], idle_between[dev], idle_edge[dev] = ins, betw, edge
        in_module[dev] = opened
    k = max(len(by_dev), 1)

    def mean(acc):
        return {name: ns / k for name, ns in acc.most_common()}
    return ScopeReduction(
        base, role_ns, no_role, idle_in, idle_between, idle_edge, in_module,
        mean(scope_acc),
        mean(prog_acc), mean(pair_acc), mean(in_role_acc),
        [s for s in croft_spans if s.end > lo and s.start < hi])


def metrics(red: ScopeReduction, steps: int) -> dict:
    """The four per-step metrics this module adds, ``unscoped_ms`` (time
    of ops the program gave no role) and ``xla_moves_ms`` (the part of
    it that is XLA's own data movement, ``(xla)``); each a mean over the
    devices, in ms per step."""
    if not steps or not red.role_ns:
        return {}

    def per_step(ns):
        return ns / 1e6 / steps
    return {"dft_ms": per_step(red.mean("role_ns", "croft.dft")),
            "relayout_ms": per_step(red.mean("role_ns", "croft.relayout")),
            "idle_in_program_ms": per_step(red.mean("idle_in_program_ns")),
            "idle_between_programs_ms": per_step(
                red.mean("idle_between_ns")),
            "unscoped_ms": per_step(red.mean("no_role_ns")),
            "xla_moves_ms": per_step(red.mean("role_ns", XLA_TAG))}


def breakdown(red: ScopeReduction, steps: int, n: int = 10) -> dict:
    """``scopes``: the top ``n`` stage/chunk/role paths by device time
    and the unscoped time; ``programs``: device time of each program;
    ``idle_boundaries``: idle by program pair, the part of it inside a
    program's ``XLA Modules`` interval, and in-program idle by the role
    of the op before the gap.  All in ms per step."""
    def ms(d, top=None):
        items = [(k, v) for k, v in d.items() if k != UNSCOPED][:top]
        out = [[k, v / 1e6 / steps] for k, v in items]
        if UNSCOPED in d:
            out.append([UNSCOPED, d[UNSCOPED] / 1e6 / steps])
        return out
    return {"scopes": ms(red.scope_ns, n),
            "programs": ms(red.program_ns),
            "idle_boundaries": {
                "between_programs": ms(red.idle_pairs),
                "between_inside_a_module": red.mean(
                    "idle_between_in_module_ns") / 1e6 / steps,
                "in_program_after": ms(red.idle_in_by_role)}}


# -- one cell on the chip --------------------------------------------------

ENTRIES = ("forward", "inverse", "forward_filtered")


def _traced(step, seconds: float, hlo_for):
    """One window under ``jax.profiler`` with the program's spans on its
    clock; (steps, elapsed, what :func:`load_xplane` gives)."""
    import shutil
    import tempfile

    import jax

    from repro.obs import tracer

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    log_dir = tempfile.mkdtemp(prefix="bench-scopes-")
    try:
        with jax.profiler.trace(log_dir, profiler_options=opts):
            with tracer.profiler_sink():
                steps, elapsed = step.window(seconds)
        loaded = load_xplane(trace_reduce.find_xplane(log_dir), hlo_for)
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    return steps, elapsed, loaded


def main(argv=None) -> int:
    """One cell on the chip; see the module's docstring.  To be deleted
    when ``bench/harness.py`` reads these metrics itself."""
    import argparse
    import json
    import time

    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    from bench import harness, system
    from bench.run import pin_host_cores

    pin_host_cores()
    bm = harness.load_benchmark()
    cell = harness.find_cell(bm, args.workload)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"trace_scopes: {args.workload} needs {cell['chips']} TPU "
              f"chips, JAX found {len(devices)} {devices[0].platform}",
              file=sys.stderr)
        return 2
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    devices = devices[:cell["chips"]]
    cfg, cfg_module = harness.config(cell["config"])
    tr = harness.traffic(cell["name"])
    sut = system.Program(cfg, devices)
    step = harness.step_kind(tr["step"]).Step(sut, cfg, tr, args.seed,
                                              cfg_module)
    step.setup()
    setup_s = time.perf_counter() - t_start
    seconds = min(args.seconds, float(tr.get("trace_seconds", args.seconds)))

    def hlo_for(mods):
        texts = {}
        for entry in ENTRIES:
            mod = "jit_croft_" + entry
            if mod in mods:
                texts[mod] = sut.plan.lower(entry).compile().as_text()
        return texts

    steps, elapsed, (ops, spans, croft, runs) = _traced(step, seconds,
                                                       hlo_for)
    red = reduce(ops, spans, croft, runs=runs)
    from bench.peaks import peaks_for

    ctx = harness.MetricContext(
        trace=red.base, steps=steps, window_s=elapsed,
        least_hbm_bytes=cfg_module.least_hbm_bytes(cfg, tr),
        peaks=peaks_for(devices[0].device_kind))
    layer = {}
    for m in harness.per_layer_for(bm, cell):
        v = harness.metric_reader(m["name"]).read(ctx)
        if v is not None:
            layer[m["name"]] = v
    layer.update(metrics(red, steps))
    busy = red.base.mean("busy_ns")
    out = {
        "workload": args.workload, "seed": args.seed,
        "device": {"kind": devices[0].device_kind, "count": len(devices)},
        "setup_s": setup_s,
        "window": {"seconds": elapsed, "steps": steps,
                   "traced_step_ms": elapsed * 1e3 / steps,
                   "window_s": red.base.window_ns / 1e9,
                   "busy_s": busy / 1e9},
        "metrics": layer,
        "unscoped_share_of_busy": (red.mean("no_role_ns") / busy
                                   if busy else None),
        "idle_edge_ms": red.mean("idle_edge_ns") / 1e6 / steps,
        "ops": len(ops),
        "unscoped_ops": _top_unscoped(ops, steps),
        "croft_spans_ms": {
            name: sum(s.end - s.start for s in red.croft_spans
                      if s.name == name) / 1e6 / steps
            for name in sorted({s.name for s in red.croft_spans})},
        "breakdown": {"device_ops": red.base.top_ops(10),
                      "idle_gaps": red.base.top_gaps(10),
                      **breakdown(red, steps)},
    }
    step.release()
    print(json.dumps(out))
    return 0


def _top_unscoped(ops, steps: int, n: int = 10) -> list:
    """[label, ms per step] of the ops without a role with the most time,
    over all devices."""
    acc: collections.Counter = collections.Counter()
    for op in ops:
        if role_of(op.scope) in (XLA_TAG, UNSCOPED):
            acc[f"{op.program}:{op.name}"] += op.end - op.start
    return [[k, v / 1e6 / steps] for k, v in acc.most_common(n)]


if __name__ == "__main__":
    sys.exit(main())
