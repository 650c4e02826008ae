"""Run one cell of ``BENCHMARK.json`` once and build its result line.

Everything that belongs to one configuration, traffic mix, step kind or
per-layer metric sits in a file of its own, found by name:

    bench/configs/<config>.json   sizes, layout, guarantees and limits
    bench/configs/<config>.py     its least-bytes function, and whatever
                                  else the step kind asks of it
    bench/traffic/<cell>.json     the traffic: step kind and parameters
                                  (``trace_seconds``: the part of the
                                  window a ``--trace 1`` run traces)
    bench/steps/<kind>.py         the step kind: set-up, window, check
    bench/metrics/<metric>.py     ``read(ctx)`` of one per-layer metric

so a new cell, configuration or metric is new files and no edit here.
Nothing in this module asks for a TPU; ``bench/run.py`` does.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import re
import shutil
import sys
import tempfile
import threading
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
GIB = 2 ** 30
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration")
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


# -- files, by name -----------------------------------------------------------

def load_benchmark(root=ROOT) -> dict:
    return json.loads((pathlib.Path(root) / "BENCHMARK.json").read_text())


def load_module(path: pathlib.Path):
    """Import a file whose name need not be an identifier."""
    name = "bench_file_" + re.sub(r"\W", "_", str(path.relative_to(
        path.parents[1])))
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


def find_cell(bm: dict, name: str) -> dict:
    for cell in bm["workloads"]:
        if cell["name"] == name:
            return cell
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                   f"{[c['name'] for c in bm['workloads']]}")


def config(name: str, bench=BENCH) -> tuple[dict, object]:
    """(configuration dict, its module) of configuration ``name``."""
    cfg = json.loads((bench / "configs" / f"{name}.json").read_text())
    return cfg, load_module(bench / "configs" / f"{name}.py")


def traffic(cell_name: str, bench=BENCH) -> dict:
    return json.loads((bench / "traffic" / f"{cell_name}.json").read_text())


def step_kind(kind: str, bench=BENCH):
    return load_module(bench / "steps" / f"{kind}.py")


def metric_reader(name: str, bench=BENCH):
    return load_module(bench / "metrics" / f"{name}.py")


def _applies(metric: dict, cell: dict, bm: dict) -> bool:
    if "workloads" in metric:
        return cell["name"] in metric["workloads"]
    moved = metric.get("moves")
    if moved is None:
        return True
    e2e = {m["name"]: m for m in bm["end_to_end"]}
    return moved in e2e and _applies(e2e[moved], cell, bm)


def end_to_end_for(bm: dict, cell: dict) -> list:
    return [m for m in bm["end_to_end"] if _applies(m, cell, bm)]


def per_layer_for(bm: dict, cell: dict) -> list:
    return [m for m in bm["per_layer"] if _applies(m, cell, bm)]


# -- measuring ------------------------------------------------------------------

class CompileLog:
    """Compilations (tracing or backend compiles) and persistent-cache hits
    that JAX reports while open, on any thread."""

    def __init__(self):
        self.compiles = 0
        self.seconds = 0.0
        self.cache_hits = 0
        self._lock = threading.Lock()

    def _on_duration(self, event, duration, **_):
        if event in COMPILE_EVENTS:
            with self._lock:
                self.compiles += 1
                self.seconds += duration

    def _on_event(self, event, **_):
        if event == CACHE_HIT_EVENT:
            with self._lock:
                self.cache_hits += 1

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)


def peak_bytes(devices) -> int | None:
    """``peak_bytes_in_use`` of the fullest device, None where the backend
    does not report it."""
    peaks = []
    for d in devices:
        stats = d.memory_stats()
        if stats and "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


@dataclasses.dataclass
class MetricContext:
    """What a per-layer metric's ``read`` gets."""
    trace: object                 # trace_reduce.Reduction, or None
    steps: int
    window_s: float
    least_hbm_bytes: int
    peaks: dict | None            # bench/peaks.py; None off a TPU


def _traced_window(step, seconds: float):
    """Run the window under ``jax.profiler``; (steps, elapsed, Reduction,
    seconds spent writing, reading and reducing the trace)."""
    import jax

    from bench import trace_reduce

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    log_dir = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        with jax.profiler.trace(log_dir, profiler_options=opts):
            steps, elapsed = step.window(seconds)
            t_end = time.perf_counter()
        ops, spans = trace_reduce.load_xplane(
            trace_reduce.find_xplane(log_dir))
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    reduction = trace_reduce.reduce(ops, spans)
    return steps, elapsed, reduction, time.perf_counter() - t_end


def run_cell(cell: dict, *, seed: int, seconds: float, trace: bool,
             devices, t_start: float, bm: dict | None = None,
             system_factory=None, bench=BENCH, cfg_override=None) -> dict:
    """One run of ``cell``: set-up, window, check.  Returns the result line
    as a dict.  ``system_factory(cfg, devices)`` puts something else in the
    program's place (the control, a planted fault); ``cfg_override``
    changes configuration keys (a tiny size for the tests)."""
    from bench import compare, peaks as peaks_lib, system as system_lib

    bm = bm if bm is not None else load_benchmark(bench.parent)
    cfg, cfg_module = config(cell["config"], bench)
    if cfg_override:
        cfg = {**cfg, **cfg_override}
    tr = traffic(cell["name"], bench)
    kind = step_kind(tr["step"], bench)
    factory = system_factory or system_lib.Program
    with CompileLog() as setup_log:
        sut = factory(cfg, devices)
        step = kind.Step(sut, cfg, tr, seed, cfg_module)
        step.setup()
    setup_s = time.perf_counter() - t_start

    reduction = None
    with CompileLog() as window_log:
        if trace:
            # a trace holds every device op: a cell of short steps traces
            # a part of the window, so that reading it stays short
            traced_s = min(seconds, float(tr.get("trace_seconds", seconds)))
            steps, elapsed, reduction, trace_s = _traced_window(step,
                                                                traced_s)
        else:
            steps, elapsed = step.window(seconds)
    peak = peak_bytes(devices)
    step.release()
    del sut
    numbers = step.check()
    over, checks = compare.verdict(numbers, cfg["guarantees"]["accuracy"])

    kind_name = devices[0].device_kind
    device = {"platform": devices[0].platform, "kind": kind_name,
              "count": len(devices), "memory_peak_bytes": peak}
    metrics, breakdown = {}, None
    if not trace:
        values = {"step_ms": elapsed * 1e3 / steps,
                  "peak_hbm_gib": None if peak is None else peak / GIB,
                  "setup_s": setup_s}
        for m in end_to_end_for(bm, cell):
            if values.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    else:
        ctx = MetricContext(
            trace=reduction, steps=steps, window_s=elapsed,
            least_hbm_bytes=cfg_module.least_hbm_bytes(cfg, tr),
            peaks=(peaks_lib.peaks_for(kind_name)
                   if devices[0].platform == "tpu" else None))
        for m in per_layer_for(bm, cell):
            value = metric_reader(m["name"], bench).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = reduction.mean("busy_ns") / 1e9
        device["window_s"] = reduction.window_ns / 1e9
        breakdown = {"device_ops": reduction.top_ops(10),
                     "idle_gaps": reduction.top_gaps(10)}
    result = {"correct": not over, "attempted": steps, "failed": len(over),
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["setup"] = {"compiles": setup_log.compiles,
                       "compile_s": setup_log.seconds,
                       "cache_hits": setup_log.cache_hits}
    result["window"] = {"seconds": elapsed, "steps": steps,
                        "compiles": window_log.compiles}
    if trace:
        result["window"]["trace_read_s"] = trace_s
    result["checks"] = checks
    return result


def format_checks(checks: dict) -> list[str]:
    """The lines printed last on standard error: each number beside its
    limit."""
    return [f"check {name}: {c['value']!r} (limit {c['limit']!r})"
            for name, c in checks.items()]
