"""The cells at tiny sizes on the CPU: the one-chip cell in this process,
the reference against numpy, the configurations' own arithmetic, and the
traffic generator."""

import functools
import json
import math
import time

import numpy as np
import pytest

import jax

from bench import harness, reference, system, traffic


def run(cell_name, bm, tiny, seconds=0.3, trace=False, factory=None,
        seed=2 ** 33 + 5):
    cell = harness.find_cell(bm, cell_name)
    return harness.run_cell(cell, seed=seed, seconds=seconds, trace=trace,
                            devices=jax.devices()[:1],
                            t_start=time.perf_counter(), bm=bm,
                            system_factory=factory,
                            cfg_override=tiny[cell["config"]])


# -- least bytes, by hand -------------------------------------------------------

def test_least_bytes_pme_128():
    cfg, mod = harness.config("pme-128")
    # grid 128^3 f32 read + filter and spectrum 128*128*65 c64 read and
    # written by the filtered forward; spectrum read, grid written back
    assert mod.least_hbm_bytes(cfg, harness.traffic("pme-128.step")) == \
        42_336_256


def test_least_bytes_croft_1024():
    cfg, mod = harness.config("croft-1024")
    got = mod.least_hbm_bytes(cfg, harness.traffic("croft-1024.fwd-inv"))
    assert got == 8 * 2 ** 30          # 2 GiB in and out, twice, per chip


def test_least_bytes_refuse_other_step_kinds():
    for name in ("pme-128", "croft-1024"):
        cfg, mod = harness.config(name)
        with pytest.raises(ValueError):
            mod.least_hbm_bytes(cfg, {"step": "something_else"})


# -- the SPME influence function --------------------------------------------------

def test_spme_constants():
    cfg, mod = harness.config("pme-128")
    # GROMACS at rcoulomb 1.0, ewald-rtol 1e-5: 1/beta = 0.320163 nm
    assert 1 / mod.ewald_beta(1e-5, 1.0) == pytest.approx(0.320163, abs=1e-6)
    assert mod.bspline_values(4) == pytest.approx([0, 1 / 6, 2 / 3, 1 / 6, 0])
    b = mod.bspline_moduli(128, 4)
    assert b[0] == pytest.approx(1.0)
    assert np.all(b >= 1.0) and np.all(np.isfinite(b))


def test_spme_filter_shape_and_symmetry(tiny):
    cfg, mod = harness.config("pme-128")
    g = mod.kspace_filter(cfg)
    assert g.shape == (128, 128, 65) and g[0, 0, 0] == 0
    assert np.all(g >= 0) and np.isfinite(g).all()
    # even in kx and ky, so a real grid gives a real potential
    assert np.allclose(g[1:, :, :], g[1:, :, :][::-1, :, :])
    assert np.allclose(g[:, 1:, :], g[:, 1:, :][:, ::-1, :])


# -- the reference against numpy --------------------------------------------------

@pytest.mark.parametrize("shape", [(16, 8, 32), (8, 8, 8)])
def test_reference_matches_numpy(shape):
    rng = np.random.default_rng(1)
    x = (rng.standard_normal(shape)
         + 1j * rng.standard_normal(shape)).astype(np.complex64)
    y = np.asarray(reference.jitted("fft3", "highest")(x))
    want = np.fft.fftn(x.astype(np.complex128))
    assert np.abs(y - want).max() / np.abs(want).max() < 1e-6
    back = np.asarray(reference.jitted("ifft3", "highest")(y))
    assert np.abs(back - x).max() / np.abs(x).max() < 1e-6
    r = x.real.copy()
    h = rng.standard_normal(shape[:2] + (shape[2] // 2 + 1,))
    got = np.asarray(reference.jitted("filtered_round_trip", "highest",
                                      nz=shape[2])(r, h.astype(np.complex64)))
    want = np.fft.irfftn(np.fft.rfftn(r) * h, s=shape)
    assert np.abs(got - want).max() / np.abs(want).max() < 1e-6


def test_reference_high_is_coarser_than_highest():
    rng = np.random.default_rng(2)
    x = (rng.standard_normal((16, 16, 16))
         + 1j * rng.standard_normal((16, 16, 16))).astype(np.complex64)
    want = np.fft.fftn(x.astype(np.complex128))
    err = {p: np.abs(np.asarray(reference.jitted("fft3", p)(x)) - want).max()
           / np.abs(want).max() for p in reference.PRECISIONS}
    assert err["highest"] < 1e-6 < 1e-5 < err["high"] < 1e-3


# -- the traffic generator --------------------------------------------------------

def test_seeds_past_32_bits_do_not_wrap():
    a = traffic.fields(5, 1, (4, 4, 4), "float32")[0]
    b = traffic.fields(5 + 2 ** 32, 1, (4, 4, 4), "float32")[0]
    c = traffic.fields(5, 1, (4, 4, 4), "float32")[0]
    assert not np.array_equal(a, b)
    assert np.array_equal(a, c)


def test_pool_field_does_not_depend_on_pool_size():
    big = traffic.fields(9, 4, (4, 4, 4), "complex64")
    small = traffic.fields(9, 2, (4, 4, 4), "complex64")
    assert np.array_equal(big[1], small[1])
    assert np.iscomplexobj(big[0]) and np.abs(big[0].imag).max() > 0


# -- the one-chip cell, end to end --------------------------------------------------

def test_pme_cell_end_to_end(bm, tiny):
    res = run("pme-128.step", bm, tiny)
    assert res["correct"] and res["failed"] == 0
    assert res["attempted"] > 8
    # the CPU reports no peak memory, so peak_hbm_gib is left out here
    assert set(res["metrics"]) == {"step_ms", "setup_s"}
    assert res["window"]["compiles"] == 0
    assert list(res)[-1] == "checks"
    assert res["checks"]["potential_err"]["value"] < 1e-6
    json.dumps(res, allow_nan=False)


def test_pme_cell_traced(bm, tiny):
    res = run("pme-128.step", bm, tiny, trace=True)
    assert res["correct"]
    assert {"dispatch_ms", "fft_compute_ms", "device_idle_frac"} <= set(
        res["metrics"])
    assert "collective_ms" not in res["metrics"]
    dev = res["device"]
    assert 0 < dev["busy_s"] <= dev["window_s"]
    assert len(res["breakdown"]["device_ops"]) <= 10
    assert len(res["breakdown"]["idle_gaps"]) <= 10


def test_pme_control_is_not_correct(bm, tiny):
    """The reference at three bfloat16 passes in the program's place."""
    res = run("pme-128.step", bm, tiny, factory=functools.partial(
        system.Reference, precision="high"))
    assert not res["correct"]
    assert res["checks"]["potential_err"]["value"] > \
        res["checks"]["potential_err"]["limit"]


class _Broken:
    """The program with one fault planted in what it returns."""

    def __init__(self, cfg, devices, fault):
        self.inner = system.Program(cfg, devices)
        self.fault = fault
        self.mesh = self.inner.mesh
        self.input_sharding = self.inner.input_sharding
        self.output_sharding = self.inner.output_sharding

    def _out(self, v):
        if self.fault == "altered":
            return v.at[(0,) * v.ndim].add(1e-3 * jax.numpy.abs(v).max())
        if self.fault == "half":
            return v.at[: v.shape[0] // 2].set(0)
        return v

    def forward(self, x):
        return x if self.fault == "unchanged" else self.inner.forward(x)

    def forward_filtered(self, x, h):
        if self.fault == "unchanged":
            return x
        return self.inner.forward_filtered(x, h)

    def inverse(self, y):
        if self.fault == "unchanged":
            return y
        return self._out(self.inner.inverse(y))

    def release(self):
        self.inner.release()


@pytest.mark.parametrize("fault", ["unchanged", "altered", "half"])
def test_pme_faults_are_not_correct(bm, tiny, fault):
    res = run("pme-128.step", bm, tiny,
              factory=functools.partial(_Broken, fault=fault))
    assert not res["correct"], res["checks"]


def test_numbers_without_limit_or_finite_value_fail():
    from bench import compare
    over, table = compare.verdict({"a": 1e-7, "b": math.nan, "c": 1.0},
                                  {"a": 1e-6, "b": 1.0})
    assert over == ["b", "c"]
    assert table["b"]["value"] == "nan" and table["c"]["limit"] is None
