#!/usr/bin/env python3
"""Smoke run of the CROFT transform stack on a TPU, in one process.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # a 2x2 v5e host: the pencil path

One chip (the default) drives the main path through the entry points a
user calls:

  croft-128      ``Croft3D`` c2c forward + inverse on the paper's grid
  c2c-512        the same at 512^3, the largest power-of-two grid one
                 16 GB chip holds out of place (croft-1024 is 8 GiB at
                 c64: input plus output alone fill the chip)
  poisson-512    ``poisson_solve`` on a 512^3 packed r2c plan; its fused
                 k-space multiply is the compiled ``spectral_scale``
                 kernel
  service        a meshless ``TransformService`` answering a dozen mixed
                 c2c / r2c / filtered requests at 128^3 and 256^3
  kernels        every transform-stack Pallas kernel at a cell's line
                 length against its jnp or numpy reference, compiled to
                 Mosaic

``--chips 4`` runs only the distributed pencil transform on a 2x2
("y", "z") mesh: croft-1024 with the default ``FFTOptions`` (alltoall,
K=2) and with the ring transpose (K=1, the compiled block-rotation
kernel), each checked against plane waves whose spectrum is known in
closed form plus the round trip, and 256^3 c2c and packed r2c against
numpy.  Each output is checked to be spread over all four devices.

Each phase prints one JSON line: its errors and the tolerance they are
held to, compile seconds, the median of a few warm calls ended by
``block_until_ready``, and the device's ``peak_bytes_in_use`` since the
process started.  These are smoke numbers, not benchmark results.  The
last line is ``{"ok": true, "device": {...}}``.  An error over
tolerance, a failure or fallback counter above zero, or a backend that
is not ``tpu`` ends the run with a non-zero exit; nothing is caught.

The phases are plain functions of their sizes, so tests run them on the
CPU at tiny sizes; only :func:`main` insists on a TPU.  The compile
cache is ``$JAX_COMPILATION_CACHE_DIR`` when set, else
``<checkout>/.jax_cache`` (``repro.launch.compile_cache``).
"""

from __future__ import annotations

import argparse
import functools
import json
import math
import os
import statistics
import sys
import threading
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import (Croft3D, Decomposition, FFTOptions,  # noqa: E402
                        poisson_solve)
from repro.kernels.backend import on_tpu  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.obs import metrics as metrics_lib  # noqa: E402
from repro.serve import TransformService  # noqa: E402

#: max error over max |reference|: the bar examples/quickstart.py prints
#: f32 DFTs at Precision.HIGHEST against
TOL = 1e-4
#: kernels that only move or combine values: a rounding difference at most
TOL_ELEMENTWISE = 1e-6
NOTE = "smoke numbers, not benchmark results"
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
#: counters that must stay 0: a failed, degraded or retried plan fails
#: the smoke run instead of hiding behind a fallback
FAILURE_COUNTERS = (
    "plan_build_failures", "plan_build_fallbacks", "plan_dispatch_failures",
    "plan_quarantines", "plan_degradations", "tune_measure_failures",
    "serve_failures", "serve_dispatch_retries", "serve_nan_outputs",
    "serve_poisoned_requests", "serve_shed_requests",
    "serve_deadline_misses")
PENCIL_AXES = ("y", "z")


class CompileLog:
    """Compile seconds and persistent-cache hits JAX reports while open
    (tracing, lowering and backend compile, on any thread)."""

    def __init__(self):
        self.seconds = 0.0
        self.cache_hits = 0
        self._lock = threading.Lock()

    def _on_duration(self, event, duration, **_):
        if event in COMPILE_EVENTS:
            with self._lock:
                self.seconds += duration

    def _on_event(self, event, **_):
        if event == CACHE_HIT_EVENT:
            with self._lock:
                self.cache_hits += 1

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)


# -- helpers ----------------------------------------------------------------

def rel_err(got, ref) -> float:
    """max |got - ref| over max |ref| (host arrays)."""
    got, ref = np.asarray(got), np.asarray(ref)
    return float(np.max(np.abs(got - ref)) / np.max(np.abs(ref)))


def warm_ms(fn, *args, iters: int = 3) -> float:
    """Median wall milliseconds of ``iters`` calls, each ended by
    ``block_until_ready``."""
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append((time.perf_counter() - t0) * 1e3)
    return statistics.median(times)


def peak_bytes(devices=None):
    """``peak_bytes_in_use`` of each device (None where the backend does
    not report it); a single value for a single device."""
    devices = devices or jax.devices()[:1]
    peaks = []
    for d in devices:
        stats = d.memory_stats()
        peaks.append(None if not stats else stats.get("peak_bytes_in_use"))
    return peaks[0] if len(peaks) == 1 else peaks


def mosaic_kernels(lowered) -> int:
    """Pallas kernels compiled for the TPU in a lowered program."""
    return lowered.as_text().count("tpu_custom_call")


def random_field(shape, seed: int, complex_: bool = True) -> np.ndarray:
    rng = np.random.default_rng(seed)
    re = rng.standard_normal(shape, dtype=np.float32)
    if not complex_:
        return re
    return (re + 1j * rng.standard_normal(shape, dtype=np.float32)
            ).astype(np.complex64)


def record(phase: str, shape, problem: str, errors: dict, tol: float,
           log: CompileLog, ms: dict, **extra) -> dict:
    return {"phase": phase, "shape": list(shape), "problem": problem,
            "max_rel_err": errors, "tol": tol,
            "compile_s": log.seconds, "cache_hits": log.cache_hits,
            "warm_ms": ms, "peak_bytes_in_use": peak_bytes(), **extra,
            "note": NOTE}


def check(rec: dict) -> dict:
    """Raise if any of the phase's errors is over its tolerance."""
    tol = rec["tol"]
    for name, err in rec["max_rel_err"].items():
        t = tol[name] if isinstance(tol, dict) else tol
        if not err <= t:
            raise RuntimeError(f"{rec['phase']}: {name} error {err} is over "
                               f"its tolerance {t}")
    return rec


def check_counters(snapshot: dict) -> dict:
    """The failure/fallback counters of a metrics snapshot; raise if any
    is above zero."""
    counts = {name: snapshot.get(name, {}).get("value", 0)
              for name in FAILURE_COUNTERS}
    bad = {k: v for k, v in counts.items() if v}
    if bad:
        raise RuntimeError(f"failure or fallback counters above zero: {bad}")
    return counts


# -- one-chip phases --------------------------------------------------------

def phase_c2c(phase: str, n: int, *, seed: int = 0, iters: int = 3) -> dict:
    """Meshless c2c forward + inverse against ``numpy.fft.fftn``."""
    plan = Croft3D((n, n, n))
    x = random_field(plan.shape, seed)
    xd = jax.device_put(x)
    with CompileLog() as log:
        y = jax.block_until_ready(plan.forward(xd))
        xb = jax.block_until_ready(plan.inverse(y))
    ms = {"forward": warm_ms(plan.forward, xd, iters=iters),
          "inverse": warm_ms(plan.inverse, y, iters=iters)}
    errors = {"forward_vs_numpy": rel_err(y, np.fft.fftn(x)),
              "round_trip": rel_err(xb, x)}
    return check(record(phase, plan.shape, "c2c", errors, TOL, log, ms))


def phase_poisson(n: int, *, iters: int = 3) -> dict:
    """``poisson_solve`` through a packed r2c plan against the
    manufactured solution u = sin(x) cos(2y) sin(3z) (examples/
    spectral_solver.py): the fused k-space multiply is the
    ``spectral_scale`` kernel on a TPU."""
    plan = Croft3D((n, n, n), problem="r2c", strategy="packed")
    g = 2 * np.pi * np.arange(n) / n
    u_true = (np.sin(g)[:, None, None] * np.cos(2 * g)[None, :, None]
              * np.sin(3 * g)[None, None, :])
    f = jax.device_put((-(1 + 4 + 9) * u_true).astype(np.float32))
    spec = (jax.ShapeDtypeStruct(plan.shape, plan.input_dtype),
            jax.ShapeDtypeStruct(plan.spectrum_shape, plan.dtype))
    with CompileLog() as log:
        u = jax.block_until_ready(poisson_solve(f, plan))
    kernels = mosaic_kernels(plan._filtered_fn().lower(*spec))
    ms = {"poisson_solve": warm_ms(poisson_solve, f, plan, iters=iters)}
    errors = {"vs_manufactured": rel_err(u, u_true)}
    return check(record("poisson-%d" % n, plan.shape, "r2c-filtered", errors,
                        TOL, log, ms, strategy=plan.strategy,
                        mosaic_kernels=kernels))


def phase_service(sizes=(128, 256), *, seed: int = 0, rounds: int = 2
                  ) -> dict:
    """A meshless ``TransformService`` answering 6 requests per size
    (c2c, r2c, filtered, twice each), every result against numpy; the
    same requests again in a second, warm round."""
    reqs = []
    for i, n in enumerate(sizes):
        for j in range(2):
            s = seed + 10 * i + 3 * j
            x = random_field((n, n, n), s)
            reqs.append(("c2c", x, {}, np.fft.fftn(x)))
            r = random_field((n, n, n), s + 1, complex_=False)
            reqs.append(("r2c", r, {"problem": "r2c"}, np.fft.rfftn(r)))
            xf = random_field((n, n, n), s + 2)
            h = random_field((n, n, n), s + 3)
            reqs.append(("filtered", xf, {"problem": "filtered", "h": h},
                         np.fft.fftn(xf) * h))
    errors = {p: 0.0 for p in ("c2c", "r2c", "filtered")}
    latency_ms, compile_s = [], []
    svc = TransformService(None, max_batch=4, max_wait_ms=5.0)
    with CompileLog() as log, svc:
        for _ in range(rounds):
            before = log.seconds
            futs = [svc.submit(x, **kw) for _, x, kw, _ in reqs]
            results = [f.result(timeout=900) for f in futs]
            for res, (problem, _, _, ref) in zip(results, reqs):
                if not res.ok:
                    raise RuntimeError(f"service {problem} request failed: "
                                       f"{res.error}")
                errors[problem] = max(errors[problem], rel_err(res.value, ref))
            latency_ms.append([r.latency_s * 1e3 for r in results])
            compile_s.append(log.seconds - before)
        counters = check_counters(svc.registry.snapshot())
        stats = svc.stats()
    ms = {"request_latency_p50_warm_round":
          statistics.median(latency_ms[-1])}
    return check(record(
        "service", sizes, "mixed", errors, TOL, log, ms,
        requests=len(reqs) * rounds, batches=stats["batches"],
        compile_s_per_round=compile_s, counters=counters))


def phase_kernels(*, n: int = 1024, rows: int = 4096) -> dict:
    """Each transform-stack Pallas kernel at line length ``n`` against its
    jnp or numpy reference; on a TPU each must lower to a Mosaic kernel
    (``tpu_custom_call``), i.e. none runs interpreted."""
    from repro.kernels import ops, spectral_scale, transpose_pack
    from repro.real import packing

    errors, tol, kernels = {}, {}, {}

    def run(name, fn, args, ref, t):
        jitted = jax.jit(fn)
        kernels[name] = mosaic_kernels(jitted.lower(*args))
        if on_tpu() and not kernels[name]:
            raise RuntimeError(f"kernel {name} did not compile to Mosaic")
        errors[name] = rel_err(jitted(*args), ref)
        tol[name] = t

    with CompileLog() as log:
        x = random_field((rows, n), 1)
        xp = np.stack([x.real, x.imag])  # the executor's stacked planes
        run("rotate_block_rows", lambda a: transpose_pack.rotate_blocks(
            a, 1, 1, 2, use_pallas=True), (jnp.asarray(xp),),
            np.concatenate([xp[:, rows // 2:], xp[:, :rows // 2]], axis=1),
            TOL_ELEMENTWISE)
        c = jnp.asarray(random_field((rows // 2, 2, n), 2))
        half = packing.unpack_two(c, 1, fold=True)
        planes = lambda v: np.stack([np.real(v), np.imag(v)])
        run("hermitian_unpack", lambda a: packing.unpack_two_planes(
            a, 2, use_pallas=True), (jnp.asarray(planes(c)),),
            planes(half), TOL_ELEMENTWISE)
        run("hermitian_extend", lambda a: packing.repack_halves_planes(
            a, 2, n, use_pallas=True), (jnp.asarray(planes(half)),),
            planes(packing.repack_halves(half, 1, n, folded=True)),
            TOL_ELEMENTWISE)
        for m in sorted({64, 256, n}):
            xm = random_field((rows, m), 3)
            run(f"fft4step_{m}", ops.fft_matmul_1d, (jnp.asarray(xm),),
                np.fft.fft(xm), TOL)
        h = random_field((rows, n), 4)
        run("spectral_scale", functools.partial(
            spectral_scale.spectral_scale, use_pallas=True),
            (jnp.asarray(x), jnp.asarray(h)), x * h, TOL_ELEMENTWISE)
    return check(record("kernels", [rows, n], "pallas", errors, tol, log, {},
                        mosaic_kernels=kernels))


# -- four-chip phases -------------------------------------------------------

def pencil_mesh(n_devices: int = 4):
    """The 2x2 ("y", "z") pencil mesh over the first four devices."""
    devices = jax.devices()[:n_devices]
    side = math.isqrt(n_devices)
    return make_mesh((side, n_devices // side), PENCIL_AXES,
                     devices=devices)


def _shards(arr, mesh, sharding) -> dict:
    """Check ``arr`` is spread over every device of ``mesh`` with the
    shard shape its sharding names; return what was seen."""
    shards = arr.addressable_shards
    devices = {s.device for s in shards}
    want = tuple(sharding.shard_shape(arr.shape))
    shapes = {tuple(s.data.shape) for s in shards}
    if devices != set(mesh.devices.flat) or shapes != {want}:
        raise RuntimeError(f"output not spread over the mesh: devices "
                           f"{sorted(d.id for d in devices)}, shard shapes "
                           f"{sorted(shapes)} (want {want})")
    return {"n": len(shards), "shape": list(want)}


def plane_waves(n: int) -> list:
    """(k, amplitude) of three distinct plane waves on an n^3 grid."""
    return [((1, 2, 3), 1.0 + 0.5j),
            ((n // 2, 5 % n, (n - 7) % n), -0.25 + 1.0j),
            ((n - 1, n // 4, 0), 0.75 - 0.3j)]


def phase_pencil_planewave(phase: str, n: int, mesh, opts: FFTOptions, *,
                           iters: int = 3) -> dict:
    """croft-n c2c on the pencil mesh against plane waves: the forward
    spectrum is n^3 * a at each wave's k and zero elsewhere.  Input and
    checks stay on the devices (croft-1024 is 8 GiB)."""
    plan = Croft3D((n, n, n), mesh, Decomposition("pencil", PENCIL_AXES),
                   opts)
    waves = plane_waves(n)
    ks = np.array([k for k, _ in waves], np.int32)
    amps = np.array([a for _, a in waves], np.complex64)

    @functools.partial(jax.jit, out_shardings=plan.input_sharding)
    def make_input():
        idx = [jax.lax.broadcasted_iota(jnp.int32, plan.shape, d)
               for d in range(3)]
        x = jnp.zeros(plan.shape, jnp.complex64)
        for k, a in zip(ks, amps):
            m = (int(k[0]) * idx[0] + int(k[1]) * idx[1]
                 + int(k[2]) * idx[2]) % n
            ang = m.astype(jnp.float32) * np.float32(2 * np.pi / n)
            x = x + complex(a) * jax.lax.complex(jnp.cos(ang), jnp.sin(ang))
        return x

    @jax.jit
    def spectrum_err(y):
        expect = jnp.asarray(amps) * np.float32(n) ** 3
        e = y.at[ks[:, 0], ks[:, 1], ks[:, 2]].add(-expect)
        return jnp.max(jnp.abs(e)) / jnp.max(jnp.abs(expect))

    @jax.jit
    def round_trip_err(xb, x):
        return jnp.max(jnp.abs(xb - x)) / jnp.max(jnp.abs(x))

    kernels = mosaic_kernels(plan.lower_forward())
    if on_tpu() and opts.transpose_impl == "ring" and not kernels:
        raise RuntimeError("the ring transpose did not compile its Mosaic "
                           "block-rotation kernel")
    x = make_input()
    with CompileLog() as log:
        y = jax.block_until_ready(plan.forward(x))
        xb = jax.block_until_ready(plan.inverse(y))
    shards = {"input": _shards(x, mesh, plan.input_sharding),
              "spectrum": _shards(y, mesh, plan.output_sharding),
              "round_trip": _shards(xb, mesh, plan.input_sharding)}
    errors = {"forward_vs_plane_waves": float(spectrum_err(y)),
              "round_trip": float(round_trip_err(xb, x))}
    del xb
    ms = {"forward": warm_ms(plan.forward, x, iters=iters),
          "inverse": warm_ms(plan.inverse, y, iters=iters)}
    return check(record(
        phase, plan.shape, "c2c", errors, TOL, log, ms,
        options=opts.to_token(), shards=shards, mosaic_kernels=kernels,
        peak_bytes_per_device=peak_bytes(list(mesh.devices.flat))))


def phase_pencil_numpy(phase: str, n: int, mesh, problem: str, *,
                       seed: int = 0, iters: int = 3) -> dict:
    """c2c or packed r2c on the pencil mesh against numpy."""
    r2c = problem == "r2c"
    plan = Croft3D((n, n, n), mesh, Decomposition("pencil", PENCIL_AXES),
                   problem=problem, strategy="packed" if r2c else None)
    x = random_field(plan.shape, seed, complex_=not r2c)
    xd = jax.device_put(x, plan.input_sharding)
    with CompileLog() as log:
        y = jax.block_until_ready(plan.forward(xd))
        xb = jax.block_until_ready(plan.inverse(y))
    ref = np.fft.rfftn(x) if r2c else np.fft.fftn(x)
    errors = {"forward_vs_numpy": rel_err(y, ref),
              "round_trip": rel_err(xb, x)}
    shards = {"spectrum": _shards(y, mesh, plan.output_sharding),
              "round_trip": _shards(xb, mesh, plan.input_sharding)}
    ms = {"forward": warm_ms(plan.forward, xd, iters=iters),
          "inverse": warm_ms(plan.inverse, y, iters=iters)}
    return check(record(
        phase, plan.shape, problem, errors, TOL, log, ms, shards=shards,
        strategy=plan.strategy,
        peak_bytes_per_device=peak_bytes(list(mesh.devices.flat))))


# -- entry point ------------------------------------------------------------

def one_chip_phases():
    yield phase_kernels()
    yield phase_c2c("croft-128", 128)
    yield phase_c2c("c2c-512", 512)
    yield phase_poisson(512)
    yield phase_service((128, 256))


def four_chip_phases():
    mesh = pencil_mesh(4)
    yield phase_pencil_planewave("croft-1024-alltoall-k2", 1024, mesh,
                                 FFTOptions())
    yield phase_pencil_planewave(
        "croft-1024-ring-k1", 1024, mesh,
        FFTOptions(transpose_impl="ring", overlap_k=1))
    yield phase_pencil_numpy("pencil-256-c2c", 256, mesh, "c2c")
    yield phase_pencil_numpy("pencil-256-r2c", 256, mesh, "r2c")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the one-chip main path; 4: the 2x2 pencil "
                         "path only")
    args = ap.parse_args(argv)
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {platform!r}); "
              "nothing was run", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX found {len(devices)}", file=sys.stderr)
        return 1
    cache_dir = use_compile_cache()
    t0 = time.perf_counter()
    compile_s = 0.0
    phases = one_chip_phases() if args.chips == 1 else four_chip_phases()
    for rec in phases:
        compile_s += rec["compile_s"]
        print(json.dumps(rec), flush=True)
    counters = check_counters(metrics_lib.get_registry().snapshot())
    print(json.dumps({"phase": "summary", "wall_s": time.perf_counter() - t0,
                      "compile_s": compile_s, "compile_cache": cache_dir,
                      "counters": counters, "note": NOTE}), flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
