"""The names the transform puts on its device program (repro.obs.scopes).

Every dot, fusion and collective of a compiled plan carries exactly one
role scope in its ``op_name``; contractions are ``croft.dft``,
collectives ``croft.transpose``; schedule stages and K chunks show up as
``croft.stage.<name>/k<i>``; each ``Croft3D`` entry compiles to its own
named module.  Checked on the compiled HLO text, on one CPU device and
on 4 virtual devices.
"""

import inspect
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Croft3D
from repro.obs import scopes
from conftest import run_multidevice

ROLE_RE = re.compile(
    r"(?:^|/)(croft\.(?:dft|relayout|transpose|scale))(?=/|$)")

OPS = ("dot", "fusion", "all-to-all", "collective-permute",
       "collective-permute-start")
LINE = re.compile(r"^\s*(?:ROOT\s+)?%?[\w.\-]+ = .*? ("
                  + "|".join(re.escape(o) for o in OPS) + r")\(")


def entry_ops(plan, entry):
    """(module name, [[opcode, op_name], ...]) of every dot, fusion and
    collective in the compiled program of one ``Croft3D`` entry: the ops
    a device trace spends its time in."""
    text = plan.lower(entry).compile().as_text()
    module = text.split("\n", 1)[0].split()[1].rstrip(",")
    out = []
    for line in text.splitlines():
        m = LINE.match(line)
        if m:
            name = re.search(r'op_name="([^"]*)"', line)
            out.append([m.group(1), name.group(1) if name else ""])
    return module, out


def roles(op_name: str) -> list:
    """The one role of an op: XLA joins the op_names of the ops it merges
    with ``;``, and each must name exactly one role, the same one."""
    per_path = [ROLE_RE.findall(path) for path in op_name.split(";")]
    assert all(len(found) == 1 for found in per_path), op_name
    return sorted({found[0] for found in per_path})


#: what XLA's CPU partitioner makes anew from a shard_map body (the zero
#: buffers of the ring's CPU unpack) is named after the call, not the
#: op: ``jit(croft_forward)/shard_map/broadcast.7``
RENAMED = re.compile(r"^jit\(\w+\)/shard_map/[\w.\-]+$")


def check_ops(ops, collective_roles=(scopes.TRANSPOSE,)):
    """Every op names one role; a dot is a DFT contraction in a stage; a
    collective is a transpose, or (``collective_roles``) what XLA's
    partitioner adds for a relayout of a sharded array, which keeps that
    op's scope."""
    assert ops, "no dot, fusion or collective compiled"
    for opcode, op_name in ops:
        if RENAMED.match(op_name):
            assert opcode == "fusion", (opcode, op_name)
            continue
        found = roles(op_name)
        assert len(found) == 1, (opcode, op_name)
        if opcode == "dot":
            assert found == [scopes.DFT], op_name
            assert scopes.STAGE_PREFIX in op_name, op_name
        if opcode.startswith(("all-to-all", "collective-permute")):
            assert found[0] in collective_roles, op_name


@pytest.fixture(scope="module")
def packed_local():
    return Croft3D((16, 16, 16), problem="r2c", strategy="packed")


@pytest.mark.parametrize("entry", ["forward", "inverse", "forward_filtered"])
def test_local_packed_r2c_ops_carry_one_role(packed_local, entry):
    module, ops = entry_ops(packed_local, entry)
    assert module == f"jit_croft_{entry}"
    check_ops(ops)


@pytest.mark.parametrize("entry", ["forward", "inverse"])
def test_local_c2c_ops_carry_one_role(entry):
    plan = Croft3D((16, 8, 32))
    module, ops = entry_ops(plan, entry)
    assert module == f"jit_croft_{entry}"
    check_ops(ops)
    stages = {s for _, n in ops for s in re.findall(r"croft\.stage\.[\w+\-]+",
                                                    n)}
    assert stages == {"croft.stage.x-fft", "croft.stage.y-fft",
                      "croft.stage.z-fft"}


def test_batched_entries_have_their_own_module_names():
    plan = Croft3D((8, 8, 8))
    x = jnp.ones((2, 8, 8, 8), jnp.complex64)
    names = {kind: plan._batched_fn(kind).lower(x).as_text().split("\n")[0]
             for kind in ("forward", "inverse")}
    assert "croft_forward_batched" in names["forward"]
    assert "croft_inverse_batched" in names["inverse"]


def test_role_decorator_and_stage_scope_name_ops():
    @scopes.role(scopes.SCALE)
    def halve(v):
        return v * 0.5

    def f(v):
        with scopes.stage("s", 1):
            return halve(v)
    text = jax.jit(f).lower(jnp.ones(4)).compile().as_text()
    assert re.search(r'op_name="jit\(f\)/croft\.stage\.s/k1/croft\.scale/mul"',
                     text)
    assert halve.__name__ == "halve"


MULTI = "\n".join([
    "import re",
    f"OPS = {OPS!r}",
    f"LINE = re.compile({LINE.pattern!r})",
    inspect.getsource(entry_ops)]) + r'''
import json
from repro.core import Croft3D, Decomposition, FFTOptions
from repro.launch.mesh import make_mesh

mesh = make_mesh((2, 2), ("y", "z"))
dec = Decomposition("pencil", ("y", "z"))
plans = {
    "alltoall-k2": Croft3D((16, 16, 16), mesh, dec, FFTOptions(overlap_k=2)),
    "ring-k1": Croft3D((16, 16, 16), mesh, dec,
                       FFTOptions(overlap_k=1, transpose_impl="ring")),
    "pairwise-k1": Croft3D((16, 16, 16), mesh, dec,
                           FFTOptions(overlap_k=1, transpose_impl="pairwise")),
    "packed-r2c": Croft3D((16, 16, 16), mesh, dec, problem="r2c",
                          strategy="packed"),
}
out = {}
for label, plan in plans.items():
    for entry in ("forward", "inverse", "forward_filtered"):
        out[f"{label}/{entry}"] = entry_ops(plan, entry)
print(json.dumps(out))
'''


@pytest.fixture(scope="module")
def mesh_ops():
    return json.loads(run_multidevice(MULTI, n_devices=4)
                      .strip().splitlines()[-1])


@pytest.mark.parametrize("plan", ["alltoall-k2", "ring-k1", "pairwise-k1",
                                  "packed-r2c"])
@pytest.mark.parametrize("entry", ["forward", "inverse", "forward_filtered"])
def test_mesh_plan_ops_carry_one_role(mesh_ops, plan, entry):
    module, ops = mesh_ops[f"{plan}/{entry}"]
    assert module == f"jit_croft_{entry}"
    # the packed pipeline's DC/Nyquist plane fold and unfold run on the
    # global array, so the partitioner moves that plane between chips
    check_ops(ops, (scopes.TRANSPOSE, scopes.RELAYOUT)
              if plan == "packed-r2c" else (scopes.TRANSPOSE,))
    assert any(o.startswith(("all-to-all", "collective-permute"))
               for o, _ in ops)


def test_chunks_and_ring_rounds_are_named(mesh_ops):
    _, ops = mesh_ops["alltoall-k2/forward"]
    a2a = [n for o, n in ops if o == "all-to-all"]
    # K=2 pipelined: one all-to-all per chunk per transposing stage
    for stage in ("x-fft+xy", "y-fft+yz"):
        for k in ("k0", "k1"):
            assert sum(f"croft.stage.{stage}/{k}/croft.transpose" in n
                       for n in a2a) == 1, (stage, k, a2a)
    _, ops = mesh_ops["ring-k1/forward"]
    rounds = [n for o, n in ops if o.startswith("collective-permute")]
    # P - 1 = 1 ppermute round per ring stage on the 2x2 mesh
    for stage in ("x-fft+xy", "y-fft+yz"):
        assert sum(f"croft.stage.{stage}/croft.transpose" in n
                   for n in rounds) == 1, rounds


def test_scoped_plan_matches_numpy(packed_local):
    x = np.random.default_rng(0).standard_normal((16, 16, 16)).astype(
        np.float32)
    y = packed_local.forward(jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(y), np.fft.rfftn(x), atol=2e-4)
