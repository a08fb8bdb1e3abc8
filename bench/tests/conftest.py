"""Run from the repository root: ``python -m pytest bench/tests``.

The tests run on the CPU; the chip is never needed. The harness's look
for a TPU is replaced by one that hands over the CPU devices, so a test
drives the rest of a run.
"""

import os
import pathlib
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


@pytest.fixture(autouse=True)
def cpu_chips(monkeypatch):
    import jax

    from bench import harness

    monkeypatch.setattr(harness, "find_chips", lambda chips: jax.devices())
