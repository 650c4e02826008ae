"""Helpers for the benchmark's CPU tests.

They import the benchmark as the package ``bench`` and the program from
``src``; nothing here asks for a TPU.  Cells run at tiny sizes; the 2x2
cell runs in a child process on 4 virtual CPU devices.
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for path in (REPO, os.path.join(REPO, "src")):
    if path not in sys.path:
        sys.path.insert(0, path)

#: tiny stand-ins for the configurations' sizes
TINY = {"pme-128": {"shape": [16, 16, 16]},
        "croft-1024": {"shape": [16, 16, 16]}}


def run_four_devices(code: str, timeout: int = 600) -> str:
    """Run ``code`` in a child with 4 virtual CPU devices; its stdout."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.pathsep.join(
        [REPO, os.path.join(REPO, "src"), env.get("PYTHONPATH", "")])
    proc = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=timeout)
    if proc.returncode != 0:
        raise AssertionError(f"child failed (rc={proc.returncode})\n"
                             f"{proc.stdout}\n{proc.stderr[-4000:]}")
    return proc.stdout


@pytest.fixture(scope="session")
def tiny():
    return TINY


@pytest.fixture(scope="session")
def four_devices():
    return run_four_devices


@pytest.fixture(scope="session")
def bm():
    from bench import harness
    return harness.load_benchmark()
