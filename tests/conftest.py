"""Test harness configuration.

Tests never require real TPU hardware: JAX is pinned to the CPU
platform with 8 virtual devices so multi-device sharding — the
('v','l') CryptoMesh with GSPMD-partitioned crypto kernels, see
parallel/mesh.py and tests/test_mesh.py — compiles and executes the
same partitioned programs a v5e slice would run (minus the ICI).

The platform is pinned twice — the JAX_PLATFORMS env var and
jax.config — before any test imports jax, so the suite never takes a
chip from the process that owns it, wherever it runs.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402

# The staticcheck fixture corpus is analyzer test DATA, not a test
# suite: the cross-module registry trees under staticcheck_fixtures/
# carry miniature test_*.py files (flag-pin registries) that must
# never be collected as tests — they import modules that exist only
# relative to their own mini tree roots.
collect_ignore_glob = ["staticcheck_fixtures/*"]


@pytest.fixture(scope="session")
def jax_cpu_devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual CPU devices, got {len(devs)}"
    return devs


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: multi-minute scale tests (full-protocol N>=64 epochs); "
        "deselect with -m 'not slow'",
    )
    config.addinivalue_line(
        "markers",
        "faults: crash/partition/Byzantine-adversary suite — the ci.sh "
        "fault-regression gate runs it over a fixed seed matrix "
        "(FAULT_SEED env selects the scheduler/coalition seed)",
    )
