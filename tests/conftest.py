"""Shared test helpers.

NOTE: no XLA_FLAGS here — unit tests and benches must see the real (single)
device.  Multi-device tests spawn subprocesses with
``--xla_force_host_platform_device_count`` set (see ``run_multidevice``);
those children are CPU-mesh rehearsals pinned to ``JAX_PLATFORMS=cpu``, so
they never compete with a parent for an accelerator.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")


def run_multidevice(code: str, n_devices: int = 8, timeout: int = 480) -> str:
    """Run a python snippet in a subprocess with N virtual CPU devices.
    Returns stdout; raises on nonzero exit."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=timeout)
    if proc.returncode != 0:
        raise AssertionError(
            f"subprocess failed (rc={proc.returncode})\n"
            f"--- stdout ---\n{proc.stdout}\n--- stderr ---\n{proc.stderr[-4000:]}")
    return proc.stdout


def multidevice_report(code: str, **kw) -> dict:
    """``run_multidevice`` for a snippet that prints one ``REPORT <json>``
    line: the parsed JSON, for several tests to assert on."""
    out = run_multidevice(code, **kw)
    line = next(ln for ln in out.splitlines() if ln.startswith("REPORT "))
    return json.loads(line[len("REPORT "):])


@pytest.fixture(scope="session")
def rng():
    return np.random.RandomState(0)
