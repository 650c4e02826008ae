"""Compile the Pallas kernels and a pencil plan for a described TPU v5e.

Nothing runs.  Each test compiles at a cell's real shape for a chip
that is described, not attached, so what Mosaic or XLA would refuse on
the chip (a window past VMEM, an op Mosaic cannot lower, a kernel
inside ``shard_map`` without its varying axes) fails here, at no chip
time.

Rules this file keeps: the topology is described only inside the
module-scoped ``topo`` fixture (never at import, in ``skipif`` or in
``parametrize``), the persistent compilation cache is off around these
compiles, and the kernel dispatch — which asks the backend, and this
process's backend is the CPU — is steered to Mosaic by the ``tpu``
fixture, in the test and not through an option of the program.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

from repro.core import Croft3D, Decomposition, FFTOptions
from repro.kernels import backend, fft_matmul, flash_attention, hermitian
from repro.kernels import ns_update, spectral_scale, transpose_pack
from repro.solvers.navier_stokes import NavierStokes

F32 = jnp.float32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # no compiler log files
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc


@pytest.fixture(scope="module")
def tpu(topo):
    """Kernel dispatch steered to Mosaic, persistent cache off."""
    from jax.experimental.compilation_cache import compilation_cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(backend, "on_tpu", lambda: True)
        yield topo
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(tpu):
    return SingleDeviceSharding(tpu.devices[0])


def _planes(sharding, shape, count):
    return [jax.ShapeDtypeStruct(shape, F32, sharding=sharding)] * count


def _compile(fn, *specs) -> str:
    """Compiled HLO text; asserts a Mosaic kernel is in it."""
    text = jax.jit(fn).lower(*specs).compile().as_text()
    assert "tpu_custom_call" in text
    return text


# 1024^3 on 2x2: the ring transpose rotates the two stacked (1024,
# 512 * 512 / K) planes of the local pencil in two row-blocks; one
# row-block of one plane is 256 MiB at K=1
@pytest.mark.parametrize("k", [1, 2])
def test_rotate_block_rows_compiles_at_croft1024_block(one_chip, k):
    shape = (2, 1024, 512 * 512 // k)
    shift = jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one_chip)
    _compile(lambda a, s: transpose_pack.rotate_block_rows_planes(a, s, 2),
             *_planes(one_chip, shape, 1), shift)


# 512^3 rows: the c2c spectrum (n = 512) and the r2c half spectrum (257)
@pytest.mark.parametrize("n", [512, 257])
def test_spectral_scale_full_compiles_at_512_rows(one_chip, n):
    _compile(lambda *p: spectral_scale.spectral_scale_planes_full(*p),
             *_planes(one_chip, (512 * 512, n), 4))


@pytest.mark.parametrize("n", [512, 1024])
def test_hermitian_unpack_compiles(one_chip, n):
    _compile(hermitian.unpack_two_for_one_planes,
             *_planes(one_chip, (n * n // 2, n), 2))


@pytest.mark.parametrize("n", [512, 1024])
def test_hermitian_extend_compiles(one_chip, n):
    _compile(hermitian.hermitian_extend_planes,
             *_planes(one_chip, (n * n // 2, n // 2), 4))


@pytest.mark.parametrize("n", [64, 128, 256, 512, 1024, 4096])
def test_fft4step_compiles(one_chip, n):
    _compile(lambda a, b: fft_matmul.fft4step_planes(a, b, -1),
             *_planes(one_chip, (4096, n), 2))


def test_fft4step_refuses_lengths_past_its_limit():
    n = 2 * fft_matmul.MAX_N
    x = jax.ShapeDtypeStruct((8, n), F32)
    with pytest.raises(ValueError, match="local_impl='matmul'"):
        jax.eval_shape(lambda a: fft_matmul.fft4step_planes(a, a), x)


# gemma3-4b's local attention layer (8 query heads over 4 kv heads,
# head_dim 256, window 1024) at 2048 tokens, bf16
def test_flash_attention_compiles_at_gemma3_local_layer(one_chip):
    q = jax.ShapeDtypeStruct((1, 2048, 8, 256), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 2048, 4, 256), jnp.bfloat16,
                              sharding=one_chip)
    _compile(lambda q, k, v: flash_attention.flash_attention(
        q, k, v, window=1024), q, kv, kv)


def test_pencil_ring_forward_compiles_on_2x2(tpu):
    """A whole 256^3 pencil forward with the ring transpose: the
    block-rotation kernel compiled inside ``shard_map``."""
    mesh = Mesh(np.array(tpu.devices).reshape(2, 2), ("y", "z"),
                axis_types=(jax.sharding.AxisType.Auto,) * 2)
    plan = Croft3D((256, 256, 256), mesh, Decomposition("pencil", ("y", "z")),
                   FFTOptions(transpose_impl="ring", overlap_k=1))
    compiled = plan.lower_forward().compile()
    assert "tpu_custom_call" in compiled.as_text()
    local = 256 ** 3 * 8 // 4
    assert compiled.memory_analysis().argument_size_in_bytes == local


def test_croft1024_forward_runs_on_planes(tpu):
    """croft-1024's forward on the 2x2 (the benchmark cell's program).
    The executor carries stacked real/imag planes, so each chunk's
    transpose is one all-to-all for both planes (4 transposing stages x
    K=2 chunks), and each 1024 = 32 x 32 FFT is two real contractions
    (the x and y FFTs per chunk, the z FFT once: 10).  The parent read
    and wrote 167.5 GB a chip in 30 convolutions and 16 all-to-alls."""
    import re
    mesh = Mesh(np.array(tpu.devices).reshape(2, 2), ("y", "z"),
                axis_types=(jax.sharding.AxisType.Auto,) * 2)
    plan = Croft3D((1024,) * 3, mesh, Decomposition("pencil", ("y", "z")),
                   FFTOptions())
    compiled = plan.lower_forward().compile()
    ops = re.findall(r"= \S+ ([\w\-]+)\(", compiled.as_text())
    assert ops.count("all-to-all") == 8
    assert ops.count("convolution") == 10
    assert compiled.cost_analysis()["bytes accessed"] <= 125e9
    assert compiled.memory_analysis().temp_size_in_bytes <= 3.5 * 2 ** 30


def test_ns_substage_fits_one_chip_at_512(one_chip):
    """dns-512's program: one RK4 substage of a 512^3 pseudo-spectral DNS
    (6 c2r and 3 r2c through the packed local plan, the fused update
    kernel) with its three (3, 512, 512, 257) complex64 state stacks
    donated.  It compiled to 4.52 GiB of arguments and 8.26 GiB of temp
    (12.8 of the chip's 16 GiB)."""
    import re
    plan = Croft3D((512,) * 3, None, problem="r2c", strategy="packed")
    ns = NavierStokes(plan.forward, plan.inverse, plan.shape, nu=1e-3,
                      dt=1e-3)
    compiled = ns.lower(one_chip).compile()
    assert re.search(r"%croft_ns_update[.\d]* = .*custom-call\(.*"
                     r'custom_call_target="tpu_custom_call"',
                     compiled.as_text())
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 14 * 2 ** 30
    # the state is donated: each of the three stacks is written in place
    assert mem.alias_size_in_bytes == 3 * 3 * 512 * 512 * 257 * 8


def _opcodes(compiled) -> list:
    import re
    return re.findall(r"= \S+ ([\w\-]+)\(", compiled.as_text())


def test_ns_substage_runs_local_fft_on_planes(one_chip):
    """dns-512's substage with the local packed transforms on stacked
    real/imag planes: each axis of each transform is two real
    contractions, and the substage makes three transform calls of three
    fields (u, omega, then u x omega): 3 x 3 x 2 = 18 convolutions.  The
    complex einsums before compiled to 36 in two calls (six fields,
    three fields) and accessed 325.8 GB; this program accesses 228.1 GB.
    One six-field c2r call would make it 12 convolutions, but that
    program takes 12.8 GiB of temporaries and does not fit the chip
    (see ``test_ns_substage_fits_one_chip_at_512``)."""
    plan = Croft3D((512,) * 3, None, problem="r2c", strategy="packed")
    ns = NavierStokes(plan.forward, plan.inverse, plan.shape, nu=1e-3,
                      dt=1e-3)
    compiled = ns.lower(one_chip).compile()
    assert _opcodes(compiled).count("convolution") == 18
    assert compiled.cost_analysis()["bytes accessed"] <= 270e9


# pme-128's two programs: the packed r2c with the fused filter and the
# c2r, each three axes of two real contractions.  On complex einsums
# they compiled to 18 convolutions each and accessed 699.7 MB (forward)
# and 457.5 MB (inverse); on planes 442.8 MB and 301.8 MB.
@pytest.mark.parametrize("entry,most_bytes", [("forward_filtered", 500e6),
                                              ("inverse", 350e6)])
def test_pme128_programs_run_on_planes(one_chip, entry, most_bytes):
    plan = Croft3D((128,) * 3, None, problem="r2c", strategy="packed")
    grid = jax.ShapeDtypeStruct((128,) * 3, F32, sharding=one_chip)
    half = jax.ShapeDtypeStruct((128, 128, 65), jnp.complex64,
                                sharding=one_chip)
    if entry == "inverse":
        compiled = jax.jit(plan.inverse).lower(half).compile()
    else:
        compiled = jax.jit(plan.forward_filtered).lower(grid, half).compile()
    assert _opcodes(compiled).count("convolution") == 6
    assert compiled.cost_analysis()["bytes accessed"] <= most_bytes


# every kernel carries its own name into the program: the device trace
# shows it as the custom call's instruction name
@pytest.mark.parametrize("name", [
    "croft_spectral_scale", "croft_spectral_scale_full",
    "croft_hermitian_unpack", "croft_hermitian_extend", "croft_fft_dense",
    "croft_fft4step", "croft_rotate_blocks", "croft_ns_update",
    "flash_attention"])
def test_kernels_carry_their_names(one_chip, name):
    planes = lambda shape, k: _planes(one_chip, shape, k)  # noqa: E731
    shift = jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one_chip)
    q = jax.ShapeDtypeStruct((1, 256, 2, 128), jnp.bfloat16,
                             sharding=one_chip)
    fn, specs = {
        "croft_spectral_scale": (
            spectral_scale.spectral_scale_planes,
            planes((64, 128), 2) + planes((128,), 2)),
        "croft_spectral_scale_full": (
            spectral_scale.spectral_scale_planes_full,
            planes((64, 128), 4)),
        "croft_hermitian_unpack": (hermitian.unpack_two_for_one_planes,
                                   planes((64, 128), 2)),
        "croft_hermitian_extend": (hermitian.hermitian_extend_planes,
                                   planes((64, 64), 4)),
        "croft_fft_dense": (lambda a, b: fft_matmul.fft4step_planes(a, b),
                            planes((64, 64), 2)),
        "croft_fft4step": (lambda a, b: fft_matmul.fft4step_planes(a, b),
                           planes((64, 1024), 2)),
        "croft_rotate_blocks": (
            lambda a, s: transpose_pack.rotate_block_rows_planes(a, s, 2),
            planes((2, 64, 128), 1) + [shift]),
        "croft_ns_update": (
            lambda c, *p: ns_update.ns_update_planes(
                c, *p, shape=(64, 128, 16), nu=1e-3),
            [jax.ShapeDtypeStruct((4,), F32, sharding=one_chip)]
            + planes((2, 3, 9, 64, 128), 4)),
        "flash_attention": (flash_attention.flash_attention, [q, q, q]),
    }[name]
    text = jax.jit(fn).lower(*specs).as_text()
    assert "tpu_custom_call" in text
    assert f'kernel_name = "{name}"' in text
