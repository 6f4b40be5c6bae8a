"""Test configuration.

Forces JAX onto a virtual 8-device CPU platform (mirroring the reference's
in-process multi-node `cluster_utils.Cluster` trick, SURVEY.md §4.2: fake
topology so collective code runs in CI without real hardware).
"""

import os
import sys

# Must happen before jax initializes its backend.
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_ENABLE_X64"] = "0"
# Tier-1 compiles thousands of CPU programs; keep them out of the checkout
# (parallel/mesh.py would put them in its in-tree cache), at a fixed path
# so reruns hit.
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      "/tmp/ray_tpu_test_jax_cache")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

try:
    import jax
    jax.config.update("jax_platforms", "cpu")
except Exception:
    pass

import pytest


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "soak: long-running chaos workload (opt-in via RAY_TPU_SOAK=1; "
        "parity: ci/long_running_tests)")
    config.addinivalue_line(
        "markers",
        "slow: long chaos soaks and other tier-2 tests excluded from "
        "the tier-1 run (-m 'not slow')")


@pytest.fixture
def ray_start():
    """Boot a real multi-process runtime for a test, like the reference's
    `ray_start_regular` fixture (`python/ray/tests/conftest.py`)."""
    import ray_tpu
    ray_tpu.init(num_cpus=4)
    yield ray_tpu
    ray_tpu.shutdown()


@pytest.fixture
def ray_local():
    import ray_tpu
    ray_tpu.init(local_mode=True)
    yield ray_tpu
    ray_tpu.shutdown()
