"""The dns-512 cell at 32^3 on the CPU: the program is correct, the
control and planted faults are not; the configuration's reference and
generator against numpy; its byte counts by hand; its per-layer metrics
on hand-built trace events of the chip's program."""

import functools
import json
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bench import harness, system, trace_reduce
from bench.peaks import peaks_for
from bench.trace_reduce import Op, Span

CELL = "dns-512.rk-stage"
TINY = {"shape": [32, 32, 32]}


def run(bm, trace=False, factory=None, seed=2 ** 33 + 11):
    cell = harness.find_cell(bm, CELL)
    return harness.run_cell(cell, seed=seed, seconds=0.3, trace=trace,
                            devices=jax.devices()[:1],
                            t_start=time.perf_counter(), bm=bm,
                            system_factory=factory, cfg_override=TINY)


def tiny_config():
    cfg, module = harness.config("dns-512")
    return {**cfg, **TINY}, module


# -- least bytes, by hand -------------------------------------------------------

def test_least_bytes_dns_512():
    cfg, mod = harness.config("dns-512")
    half = 512 * 512 * 257 * 8           # one complex64 half spectrum
    real = 512 ** 3 * 4                  # one float32 field
    # transforms 9 spectra + 9 fields; cross product 9 fields; update 18
    # spectra
    assert mod.ns_update_bytes(cfg) == 18 * half == 9_701_425_152
    assert mod.least_hbm_bytes(cfg, harness.traffic(CELL)) == \
        27 * half + 18 * real == 24_215_814_144
    with pytest.raises(ValueError):
        mod.least_hbm_bytes(cfg, {"step": "filtered_inverse"})


# -- the configuration's generator and reference against numpy -------------------

def _numpy_rhs(u_hat, nu, shape):
    k = np.stack(np.broadcast_arrays(
        np.fft.fftfreq(shape[0], 1 / shape[0])[:, None, None],
        np.fft.fftfreq(shape[1], 1 / shape[1])[None, :, None],
        np.arange(shape[2] // 2 + 1)[None, None, :]))
    k2 = np.sum(k * k, axis=0)
    keep = np.all(np.abs(k) < (2 / 3 * (np.asarray(shape) // 2 + 1))[
        :, None, None, None], axis=0)

    def cross(a, b):
        return np.stack([a[1] * b[2] - a[2] * b[1], a[2] * b[0] - a[0] * b[2],
                         a[0] * b[1] - a[1] * b[0]])
    u = np.fft.irfftn(u_hat, s=shape, axes=(1, 2, 3))
    w = np.fft.irfftn(1j * cross(k, u_hat), s=shape, axes=(1, 2, 3))
    n = np.fft.rfftn(cross(u, w), axes=(1, 2, 3)) * keep
    n = n - k * np.sum(k * n, axis=0) / np.where(k2 == 0, 1, k2)
    return n - nu * k2 * u_hat, keep, k2


def test_initial_field_and_reference_rhs():
    cfg, mod = tiny_config()
    shape = tuple(cfg["shape"])
    u_hat = np.asarray(mod.initial_field(cfg, 2 ** 40 + 3), np.complex128)
    u = np.fft.irfftn(u_hat, s=shape, axes=(1, 2, 3))
    assert np.sqrt(np.mean(u * u)) == pytest.approx(1.0, rel=1e-5)
    assert mod.divergence(cfg, jnp.asarray(u_hat, jnp.complex64)) < 1e-6
    want, keep, k2 = _numpy_rhs(u_hat, cfg["ns"]["nu"], shape)
    energy = np.sum(np.abs(u_hat) ** 2, axis=0)
    assert np.all(energy[keep & (k2 > 0)] > 0)
    assert np.all(energy[~keep] == 0) and energy[0, 0, 0] == 0
    got = np.asarray(mod.reference_rhs(cfg, jnp.asarray(u_hat,
                                                        jnp.complex64)))
    assert np.abs(got - want).max() / np.abs(want).max() < 1e-5


# -- the cell, end to end ---------------------------------------------------------

def test_ns_cell_end_to_end(bm):
    res = run(bm)
    assert res["correct"] and res["failed"] == 0
    assert res["attempted"] >= 4
    # the CPU reports no peak memory, so peak_hbm_gib is left out here
    assert set(res["metrics"]) == {"step_ms", "setup_s"}
    assert res["window"]["compiles"] == 0
    checks = res["checks"]
    assert set(checks) == {"increment_err", "div_err"}
    assert checks["increment_err"]["value"] < 1e-6
    assert checks["div_err"]["value"] < 1e-6
    json.dumps(res, allow_nan=False)


def test_ns_cell_traced(bm):
    res = run(bm, trace=True)
    assert res["correct"]
    # the update kernel runs interpreted here, so pallas_ms is the chip's
    assert {"dispatch_ms", "fft_compute_ms", "device_idle_frac"} <= set(
        res["metrics"])
    assert "collective_ms" not in res["metrics"]
    dev = res["device"]
    assert 0 < dev["busy_s"] <= dev["window_s"]


def test_ns_control_is_not_correct(bm):
    """The reference at three bfloat16 passes in the program's place."""
    res = run(bm, factory=functools.partial(system.Reference,
                                            precision="high"))
    assert not res["correct"]
    inc = res["checks"]["increment_err"]
    assert inc["value"] > inc["limit"]


@pytest.mark.parametrize("fault", ["mask off", "projection off",
                                   "curl sign flipped"])
def test_ns_faults_are_not_correct(bm, monkeypatch, fault):
    from repro.kernels import ns_update
    from repro.solvers import navier_stokes
    if fault == "mask off":
        monkeypatch.setattr(ns_update, "dealias",
                            lambda kx, ky, kz, kmax: kx == kx)
    elif fault == "projection off":
        monkeypatch.setattr(ns_update, "leray",
                            lambda n, kx, ky, kz, inv: n)
    else:
        curl = navier_stokes.curl
        monkeypatch.setattr(navier_stokes, "curl", lambda u, k: -curl(u, k))
    res = run(bm)
    assert not res["correct"], res["checks"]


# -- the per-layer metrics on the chip's events -------------------------------------

def test_ns_cell_metrics_on_known_events(bm):
    """Two substages of the chip's program: the update kernel (the cell's
    one Pallas kernel) 15 and 17 ms, a DFT fusion 400 ms each."""
    kernel = "croft_ns_update.1 custom-call:tpu_custom_call"
    ops = [Op("d0", kernel, 0, 15e6, "pallas"),
           Op("d0", "fusion.3 fusion", 15e6, 415e6, "fft"),
           Op("d0", kernel, 500e6, 517e6, "pallas"),
           Op("d0", "fusion.3 fusion", 517e6, 917e6, "fft")]
    spans = [Span("bench.window", 0, 1_000_000_000),
             Span("bench.call.ns_substage", 0, 1e6),
             Span("bench.call.ns_substage", 500e6, 501e6)]
    cfg, mod = harness.config("dns-512")
    least = mod.least_hbm_bytes(cfg, harness.traffic(CELL))
    ctx = harness.MetricContext(
        trace=trace_reduce.reduce(ops, spans), steps=2, window_s=1.0,
        least_hbm_bytes=least, peaks=peaks_for("TPU v5 lite"))
    cell = harness.find_cell(bm, CELL)
    got = {m["name"]: harness.metric_reader(m["name"]).read(ctx)
           for m in harness.per_layer_for(bm, cell)}
    assert got == pytest.approx({
        "dispatch_ms": 1.0, "fft_compute_ms": 400.0, "pallas_ms": 16.0,
        "device_idle_frac": 1 - 832e6 / 1e9,
        "hbm_floor_frac": least / 819e9 / 0.5})
