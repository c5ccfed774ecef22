"""A run that cannot measure on a TPU the benchmark knows exits non-zero
and prints no result."""
import os
import subprocess
import sys
import types

import pytest

from bench import run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def test_no_tpu_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "paper_v_a.multiset",
         "--seed", str(2**31 + 3), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert done.returncode != 0
    assert done.stdout.strip() == ""
    assert "needs a TPU" in done.stderr


@pytest.mark.parametrize("kind,count,chips,why", [
    ("TPU v9 imaginary", 1, 1, "no peaks"),
    ("TPU v5 lite", 1, 4, "asks for 4 chips"),
])
def test_unknown_device_or_too_few_chips_is_refused(monkeypatch, kind, count,
                                                    chips, why):
    import jax

    fake = [types.SimpleNamespace(platform="tpu", device_kind=kind)] * count
    monkeypatch.setattr(jax, "devices", lambda: fake)
    with pytest.raises(run.Refused, match=why):
        run.check_devices(chips, run.load_json(run.BENCH, "peaks.json"))
