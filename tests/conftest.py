"""Test harness: run everything on a virtual 8-device CPU mesh.

Multi-chip TPU hardware isn't available in CI; per SURVEY.md §4 the
multi-device code paths are validated by host simulation
(``xla_force_host_platform_device_count``).  ``JAX_PLATFORMS=cpu`` is the
explicit ask ``parallel.mesh.acquire_devices`` requires before it will
compute on the host, and it is inherited by every subprocess the tests
spawn (serving workers, CLI drives).
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: multi-process / large-compile tests"
    )


@pytest.fixture
def rng():
    return np.random.default_rng(42)


@pytest.fixture(autouse=True)
def _isolated_job_registry(tmp_path, monkeypatch):
    """Every test gets a private jobId->endpoint registry: ServingJobs
    register themselves on start (serve/registry.py), and the shared
    /tmp default would let concurrent suite runs (or a dev's live job)
    cross-talk through fixed test jobIds."""
    monkeypatch.setenv("TPUMS_REGISTRY_DIR", str(tmp_path / "job_registry"))
