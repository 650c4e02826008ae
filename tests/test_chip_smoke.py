"""chip_smoke.py's phases at tiny sizes on the CPU.

The script insists on a TPU only in ``main()``; its phases are plain
functions of their sizes, so the same code paths, references and
counter checks run here.  The four-chip pencil phases run on four
virtual CPU devices in a subprocess.
"""

import importlib.util
import os

import jax
import pytest

from conftest import REPO, run_multidevice

SMOKE_PATH = os.path.join(REPO, "chip_smoke.py")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE_PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _ok(rec):
    assert rec["max_rel_err"] and rec["compile_s"] >= 0
    assert rec["note"] == "smoke numbers, not benchmark results"
    return rec


@pytest.mark.parametrize("name,n", [("croft", 8), ("c2c", 16)])
def test_c2c_phase(smoke, name, n):
    rec = _ok(smoke.phase_c2c(name, n, iters=1))
    assert rec["shape"] == [n, n, n]
    assert set(rec["max_rel_err"]) == {"forward_vs_numpy", "round_trip"}


def test_poisson_phase(smoke):
    rec = _ok(smoke.phase_poisson(16, iters=1))
    assert rec["strategy"] == "packed"


def test_service_phase_and_its_counters(smoke):
    rec = _ok(smoke.phase_service((8, 16)))
    assert rec["requests"] == 24
    assert set(rec["max_rel_err"]) == {"c2c", "r2c", "filtered"}
    assert set(rec["counters"]) == set(smoke.FAILURE_COUNTERS)
    assert not any(rec["counters"].values())
    # the second round reuses every executable the first compiled
    assert rec["compile_s_per_round"][1] < rec["compile_s_per_round"][0]


def test_kernels_phase(smoke, monkeypatch):
    rec = _ok(smoke.phase_kernels(n=256, rows=16))
    assert {"rotate_block_rows", "hermitian_unpack", "hermitian_extend",
            "fft4step_64", "fft4step_256", "spectral_scale"} \
        == set(rec["max_rel_err"])
    # interpreted on the CPU: no Mosaic kernel in the lowered programs
    assert not any(rec["mosaic_kernels"].values())
    # on a TPU the same interpreted kernels fail the phase
    monkeypatch.setattr(smoke, "on_tpu", lambda: True)
    with pytest.raises(RuntimeError, match="did not compile to Mosaic"):
        smoke.phase_kernels(n=256, rows=16)


def test_checks_raise(smoke):
    rec = {"phase": "p", "tol": 1e-4, "max_rel_err": {"e": 2e-4}}
    with pytest.raises(RuntimeError, match="over its tolerance"):
        smoke.check(rec)
    rec["max_rel_err"]["e"] = float("nan")
    with pytest.raises(RuntimeError, match="over its tolerance"):
        smoke.check(rec)
    assert smoke.check_counters({}) == dict.fromkeys(smoke.FAILURE_COUNTERS,
                                                     0)
    with pytest.raises(RuntimeError, match="plan_build_fallbacks"):
        smoke.check_counters({"plan_build_fallbacks": {"value": 1}})


def test_main_refuses_a_backend_that_is_not_tpu(smoke, capsys):
    cache_dir = jax.config.jax_compilation_cache_dir
    assert smoke.main([]) != 0
    assert smoke.main(["--chips", "4"]) != 0
    out = capsys.readouterr()
    assert out.out == ""
    assert "no TPU" in out.err
    # it refused before turning the persistent compile cache on
    assert jax.config.jax_compilation_cache_dir == cache_dir


def test_four_chip_phases_on_virtual_devices():
    out = run_multidevice(f"""
import importlib.util
spec = importlib.util.spec_from_file_location("chip_smoke", {SMOKE_PATH!r})
smoke = importlib.util.module_from_spec(spec)
spec.loader.exec_module(smoke)
from repro.core import FFTOptions
mesh = smoke.pencil_mesh(4)
for opts in (FFTOptions(), FFTOptions(transpose_impl="ring", overlap_k=1)):
    rec = smoke.phase_pencil_planewave("pw", 16, mesh, opts, iters=1)
    assert rec["shards"]["spectrum"] == {{"n": 4, "shape": [16, 8, 8]}}, rec
for problem in ("c2c", "r2c"):
    rec = smoke.phase_pencil_numpy("np", 16, mesh, problem, iters=1)
    assert rec["shards"]["round_trip"]["n"] == 4, rec
print("FOUR_CHIP_PHASES_OK")
""", n_devices=4)
    assert "FOUR_CHIP_PHASES_OK" in out
