import faulthandler
import os
import sys

# Tests must see the real device count (1 CPU) — never the dry-run's 512.
os.environ.pop("XLA_FLAGS", None)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np
import pytest


def pytest_configure(config):
    # Hung-test watchdog: the concurrency suites exercise real lock/barrier
    # interleavings, so a regression can deadlock rather than fail. Dump
    # every thread's stack if the run wedges — CI then shows the deadlock
    # instead of a silent job kill. REPRO_TEST_DUMP_AFTER_S=0 disables.
    timeout = float(os.environ.get("REPRO_TEST_DUMP_AFTER_S", "900"))
    if timeout > 0:
        faulthandler.enable()
        faulthandler.dump_traceback_later(timeout, repeat=True, exit=False)


def pytest_unconfigure(config):
    faulthandler.cancel_dump_traceback_later()

@pytest.fixture
def rng():
    return np.random.default_rng(0)


def run_in_subprocess(code: str, n_devices: int = 8, timeout: int = 600) -> str:
    """Run ``code`` in a fresh interpreter with N virtual CPU devices."""
    import subprocess
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=timeout)
    assert out.returncode == 0, f"subprocess failed:\n{out.stdout}\n{out.stderr}"
    return out.stdout
