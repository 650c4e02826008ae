"""The 2x2 cell at a tiny size on 4 virtual CPU devices: the program is
correct, the control and each planted fault are not, and the reference
is right on the mesh.  One child process runs every case; each test
reads its own."""

import json

import pytest

CHILD = r'''
import functools, json, time
import jax, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from bench import harness, reference, system
import repro.core.schedule as schedule

bm = harness.load_benchmark()
cell = harness.find_cell(bm, "croft-1024.fwd-inv")
devices = jax.devices()[:4]
TINY = {"shape": [16, 16, 16]}


def run(factory=None, trace=False):
    return harness.run_cell(cell, seed=2 ** 35 + 1, seconds=0.3, trace=trace,
                            devices=devices, t_start=time.perf_counter(),
                            bm=bm, system_factory=factory, cfg_override=TINY)


class Broken:
    def __init__(self, cfg, devs, fault):
        self.inner = system.Program(cfg, devs)
        self.fault = fault
        self.mesh = self.inner.mesh
        self.input_sharding = self.inner.input_sharding
        self.output_sharding = self.inner.output_sharding

    def forward(self, x):
        if self.fault == "unchanged":
            return x
        y = self.inner.forward(x)
        if self.fault == "altered":
            y = y.at[1, 2, 3].add(1e-3 * jax.numpy.abs(y).max())
        return y

    def inverse(self, y):
        if self.fault == "unchanged":
            return y
        x = self.inner.inverse(y)
        if self.fault == "half":
            x = x.at[: x.shape[0] // 2].set(0)
        return x

    def release(self):
        self.inner.release()


def local_only(blk, axis, split_axis, concat_axis, impl="alltoall",
               ring_round_cb=None):
    """The transpose with the exchange between chips left out: each chip
    reshuffles its own block as if it had received its peers' pieces."""
    p = jax.lax.axis_size(axis)
    parts = jax.numpy.split(blk, p, axis=split_axis)
    return jax.numpy.concatenate(parts, axis=concat_axis)


out = {}
out["program"] = run()
out["traced"] = run(trace=True)
out["control"] = run(functools.partial(system.Reference, precision="high"))
for fault in ("unchanged", "altered", "half"):
    out[fault] = run(functools.partial(Broken, fault=fault))
real = schedule._all_to_all
schedule._all_to_all = local_only
try:
    out["no_exchange"] = run()
finally:
    schedule._all_to_all = real

mesh = jax.sharding.Mesh(np.array(devices).reshape(2, 2), ("y", "z"))
spec = (None, "y", "z")
rng = np.random.default_rng(3)
x = (rng.standard_normal((16, 8, 32))
     + 1j * rng.standard_normal((16, 8, 32))).astype(np.complex64)
xd = jax.device_put(x, NamedSharding(mesh, P(*spec)))
y = reference.jitted("fft3", "highest", mesh, spec)(xd)
want = np.fft.fftn(x.astype(np.complex128))
out["reference_mesh_err"] = float(np.abs(np.asarray(y) - want).max()
                                  / np.abs(want).max())
out["reference_mesh_spec"] = [str(a) for a in y.sharding.spec]
print(json.dumps(out))
'''


@pytest.fixture(scope="module")
def cases(four_devices):
    return json.loads(four_devices(CHILD).strip().splitlines()[-1])


def test_program_is_correct(cases):
    res = cases["program"]
    assert res["correct"], res["checks"]
    assert res["device"]["count"] == 4
    assert res["checks"]["layout_mismatches"]["value"] == 0
    assert res["window"]["compiles"] == 0
    assert set(res["metrics"]) == {"step_ms", "setup_s"}


def test_traced_run_reads_collectives(cases):
    res = cases["traced"]
    assert res["correct"]
    m = res["metrics"]
    assert {"collective_ms", "collective_exposed_ms", "fft_compute_ms",
            "dispatch_ms", "device_idle_frac"} <= set(m)
    assert "pallas_ms" not in m
    assert 0 < m["collective_exposed_ms"]["value"] <= \
        m["collective_ms"]["value"] + 1e-12


@pytest.mark.parametrize("case", ["control", "unchanged", "altered", "half",
                                  "no_exchange"])
def test_control_and_faults_are_not_correct(cases, case):
    res = cases[case]
    assert not res["correct"], res["checks"]


def test_reference_on_the_mesh(cases):
    assert cases["reference_mesh_err"] < 1e-6
    assert cases["reference_mesh_spec"] == ["None", "y", "z"]
