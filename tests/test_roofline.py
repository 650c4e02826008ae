"""Roofline machinery: trip-count-aware HLO cost analysis + term math."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.launch import hlo_cost
from repro.launch.roofline import (RooflineTerms, collective_stats,
                                   peaks_for)


def test_scan_trip_count_multiplied():
    """XLA's cost_analysis counts a scan body once; ours multiplies."""
    w = jax.ShapeDtypeStruct((256, 256), jnp.float32)

    @jax.jit
    def scanned(x):
        def body(c, _):
            return c @ x, None
        y, _ = jax.lax.scan(body, x, None, length=10)
        return y

    compiled = scanned.lower(w).compile()
    xla_flops = compiled.cost_analysis()["flops"]
    ours = hlo_cost.analyze(compiled.as_text())
    expect = 10 * 2 * 256 ** 3
    assert abs(ours.flops - expect) / expect < 0.02
    assert xla_flops < expect / 5  # documents the XLA undercount


def test_nested_scan():
    w = jax.ShapeDtypeStruct((128, 128), jnp.float32)

    @jax.jit
    def nested(x):
        def outer(c, _):
            def inner(c2, _):
                return c2 @ x, None
            c2, _ = jax.lax.scan(inner, c, None, length=5)
            return c2, None
        y, _ = jax.lax.scan(outer, x, None, length=3)
        return y

    ours = hlo_cost.analyze(nested.lower(w).compile().as_text())
    expect = 15 * 2 * 128 ** 3
    assert abs(ours.flops - expect) / expect < 0.02


def test_unrolled_matches_xla():
    w = jax.ShapeDtypeStruct((256, 256), jnp.float32)

    @jax.jit
    def unrolled(x):
        y = x
        for _ in range(4):
            y = y @ x
        return y

    compiled = unrolled.lower(w).compile()
    ours = hlo_cost.analyze(compiled.as_text())
    assert abs(ours.flops - compiled.cost_analysis()["flops"]) \
        / ours.flops < 0.02


def test_dot_flops_with_batch_dims():
    a = jax.ShapeDtypeStruct((4, 64, 32), jnp.float32)
    b = jax.ShapeDtypeStruct((4, 32, 16), jnp.float32)

    @jax.jit
    def bmm(x, y):
        return jnp.einsum("bik,bkj->bij", x, y)

    ours = hlo_cost.analyze(bmm.lower(a, b).compile().as_text())
    expect = 2 * 4 * 64 * 32 * 16
    assert abs(ours.flops - expect) / expect < 0.02


def test_roofline_terms_math():
    pk = peaks_for("TPU v5 lite")
    assert (pk.flops, pk.hbm_bw) == (197e12, 819e9)
    assert pk.link_bw == 50e9      # 1,600 Gbit/s over 4 links
    t = RooflineTerms(flops_per_device=pk.flops, bytes_per_device=pk.hbm_bw,
                      collective_bytes_per_device=pk.link_bw, n_devices=4,
                      device_kind="TPU v5 lite",
                      model_flops=4 * pk.flops * 0.5)
    assert abs(t.compute_s - 1.0) < 1e-9
    assert abs(t.memory_s - 1.0) < 1e-9
    assert abs(t.collective_s - 1.0) < 1e-9
    assert t.step_time_s == 1.0
    assert abs(t.mfu - 0.5) < 1e-9
    assert t.bottleneck in ("compute", "memory", "collective")


def test_collective_shape_parse():
    txt = """
  %ag = bf16[8,128]{1,0} all-gather(%x), replica_groups={}
  %ar = f32[64]{0} all-reduce(%y), to_apply=%add
  %a2a = c64[4,4]{1,0} all-to-all(%z)
"""
    stats = collective_stats(txt)
    assert stats["all-gather"]["bytes"] == 8 * 128 * 2
    assert stats["all-reduce"]["bytes"] == 64 * 4 * 2  # doubled
    assert stats["all-to-all"]["bytes"] == 16 * 8


def test_cost_analysis_is_per_partition():
    """Foundation of the roofline formulas (DESIGN.md §8)."""
    import os
    from conftest import run_multidevice
    run_multidevice("""
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
mesh = jax.make_mesh((8,), ("x",), axis_types=(jax.sharding.AxisType.Auto,))
sh = NamedSharding(mesh, P("x", None))
@jax.jit
def f(a):
    return a @ a.T
ca = f.lower(jax.ShapeDtypeStruct((512, 512), jnp.float32, sharding=sh)).compile().cost_analysis()
full = 2 * 512**3
# per-partition: roughly full/8 (plus collective overhead terms)
assert ca["flops"] < full / 4, ca["flops"]
print("OK per-partition flops:", ca["flops"], "vs full", full)
""")


def test_fft_collective_bytes_match_analytic_model():
    """Dry-run collective bytes == the paper's transpose-volume model."""
    from conftest import run_multidevice
    run_multidevice("""
import jax, jax.numpy as jnp
from repro.core import Croft3D, Decomposition, FFTOptions
from repro.launch import hlo_cost
mesh = jax.make_mesh((2,4), ("y","z"), axis_types=(jax.sharding.AxisType.Auto,)*2)
plan = Croft3D((32,32,32), mesh, Decomposition("pencil", ("y","z")), FFTOptions())
cost = hlo_cost.analyze(plan.lower_forward().compile().as_text())
assert abs(cost.collective_bytes - plan.comm_bytes_model()) / plan.comm_bytes_model() < 0.05, (
    cost.collective_bytes, plan.comm_bytes_model())
print("OK collective bytes", cost.collective_bytes, "model", plan.comm_bytes_model())
""")
