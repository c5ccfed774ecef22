"""Test fixtures. NOTE: no XLA_FLAGS here — tests run with 1 CPU device;
multi-device tests spawn subprocesses that set the flag themselves."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


def run_with_devices(code: str, n_devices: int = 8, timeout: int = 600) -> str:
    """Run a python snippet in a subprocess with N host devices.

    The child is held to the CPU: on a machine with a TPU the parent may
    already hold the chip, and a child reaching for it would fail or hang.
    """
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    proc = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    assert proc.returncode == 0, (
        f"subprocess failed\nSTDOUT:\n{proc.stdout}\nSTDERR:\n{proc.stderr[-3000:]}")
    return proc.stdout
