"""Test configuration: force JAX onto a virtual 8-device CPU mesh.

Multi-chip TPU hardware is not available in CI; sharding code is validated on
8 virtual CPU devices instead (SURVEY.md §7 environment facts).  These env
vars must be set before jax is imported anywhere, which is why they live at
the top of conftest rather than in a fixture.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
# The persistent XLA cache is ON by default (utils/xla_cache.py); a test run
# must not fill the checkout's .jax_cache or flip the global jax
# persistent-cache config.  setdefault so cache-specific tests (and
# developers) can still opt in explicitly.
os.environ.setdefault("GENTUN_TPU_CACHE_DIR", "off")
existing = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in existing:
    os.environ["XLA_FLAGS"] = (existing + " --xla_force_host_platform_device_count=8").strip()

import numpy as np
import pytest


def pytest_collection_modifyitems(config, items):
    """Run the multi-process cluster tests (tests/test_multihost.py) LAST.

    They dominate tier-1 wall time (each spawns a real N-process jax CPU
    cluster, ~2 min healthy and up to its 480 s join timeout when the box
    is contended), and tier-1's 870 s budget (`scripts/run_tier1.sh`)
    deliberately truncates the suite.  With alphabetical ordering the
    truncation lands mid-cluster and silently kills the entire fast tail
    (test_ops … test_xla_cache, >150 tests); slowest-last means the
    budget truncates only the cluster tests themselves, and DOTS_PASSED
    stays a meaningful floor for everything else.  Relative order within
    each group is untouched.
    """
    items.sort(key=lambda item: item.fspath.basename == "test_multihost.py")


@pytest.fixture
def rng():
    return np.random.default_rng(42)


@pytest.fixture
def tiny_images():
    """Synthetic MNIST-shaped data, small enough for CPU train steps."""
    gen = np.random.default_rng(0)
    x = gen.normal(size=(64, 8, 8, 1)).astype(np.float32)
    y = gen.integers(0, 4, size=(64,)).astype(np.int32)
    return x, y
