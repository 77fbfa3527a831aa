import os
import subprocess
import sys
import threading
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
SRC = str(REPO / "src")
if SRC not in sys.path:
    sys.path.insert(0, SRC)


def run_python(code: str, *, devices: int = 1, timeout: int = 300) -> str:
    """Run a python snippet in a fresh process with N host devices.

    Used by tests that need a different jax device count than the main
    pytest process (which stays at 1 device — the dry-run alone uses 512).
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    proc = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True,
        text=True,
        timeout=timeout,
        env=env,
    )
    if proc.returncode != 0:
        raise AssertionError(
            f"subprocess failed (rc={proc.returncode})\nstdout:\n{proc.stdout}\nstderr:\n{proc.stderr[-4000:]}"
        )
    return proc.stdout


@pytest.fixture
def subproc():
    return run_python


@pytest.fixture
def held_reclaimer(monkeypatch):
    """Hold the process's reclaimer before each pass until the test sets the
    returned event (set again at teardown, so a failing test frees it)."""
    from repro.checkpoint.cas import Reclaimer

    gate = threading.Event()
    real = Reclaimer._pass

    def held(self, *args):
        gate.wait(timeout=60)
        real(self, *args)

    monkeypatch.setattr(Reclaimer, "_pass", held)
    try:
        yield gate
    finally:
        gate.set()
