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

# the compile tests (test_tpu_compile*.py) describe a TPU from several
# pytest-xdist workers at once: without this variable only ONE process at a
# time may load the TPU library (tpu_compile_support.py). A default of the
# test harness, not of the program; a run with no xdist is one process and
# does not need it
os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")

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

from tpu_compile_support import one_chip, ssb  # noqa: E402,F401 (fixtures)


# the test files that take minutes, longest first (junit seconds of the
# driver's run before they were split out of test_tpu_compile.py). Under
# `--dist loadfile` a file is one worker's unit of work, and xdist hands the
# units out by their NUMBER of tests, most first: these, of one to three
# cases each, would start last and the whole run would wait for them
_LONG_FILES = ("test_tpu_compile_merge_values.py",   # 270
               "test_tpu_compile_sparse_scan.py",    # 203
               "test_tpu_compile_merge.py",          # 182
               "test_tpu_compile_sorted_table.py",   # 153
               "test_tpu_compile_merge_cut.py")      # 86


def pytest_collection_modifyitems(config, items):
    """Longest files first, the rest in collection order (xdist's reorder
    by count is switched off in pytest_configure)."""
    rank = {name: i for i, name in enumerate(_LONG_FILES)}
    items.sort(key=lambda it: rank.get(it.path.name, len(rank)))


def pytest_configure(config):
    if hasattr(config.option, "loadscopereorder"):
        config.option.loadscopereorder = False  # see _LONG_FILES
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
