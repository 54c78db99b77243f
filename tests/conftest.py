"""Test harness config.

Tests run on CPU with 8 virtual devices so multi-chip sharding paths
(shard_map over a Mesh) are exercised without TPU hardware, mirroring how the
driver dry-runs the multichip path. Must set env vars BEFORE jax import.
"""

import os

# tests run on the CPU whatever accelerator the machine has
os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (xla_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

# persistent compile cache: the suite re-traces the same kernel shapes every
# run. JAX_COMPILATION_CACHE_DIR, where set, is where it lives (JAX reads
# the variable itself); otherwise a fixed directory in the checkout
if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    jax.config.update(
        "jax_compilation_cache_dir",
        os.path.join(os.path.dirname(__file__), "..", ".jax_cache"))
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running tests excluded from the tier-1 gate")
    config.addinivalue_line(
        "markers", "mesh: multi-device mesh execution parity/perf tests "
                   "(need >1 virtual device; see test_mesh_parity.py)")
    config.addinivalue_line(
        "markers", "rebalance: durable segment-rebalance tests (engine, "
                   "actuator triggers, make-before-break invariants); "
                   "smoke-speed ones stay in the tier-1 gate")
    config.addinivalue_line(
        "markers", "tiered: tiered-storage tests (byte-budgeted local "
                   "cache, cold lazy loads, eviction lifecycle, prefetch); "
                   "smoke-speed ones stay in the tier-1 gate")
    config.addinivalue_line(
        "markers", "gate: perf-gate smoke over bench round payloads "
                   "(bench_gate verdict; fails on correctness flips)")


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(42)
