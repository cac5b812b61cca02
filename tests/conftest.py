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

# The driver runs six xdist workers at once, and some tests start processes of their own.  OpenMP's and OpenBLAS's
# pools (sklearn's HistGradientBoosting under ``models/boosting.py``, numpy) take every core each and spin at their
# barriers: under that load ``tests/test_boosting_model.py`` took 1,019 s against 18 s alone (PR 45's junit tables in
# CHANGES.md).  One thread a pool is what a worker can have; child processes inherit it.  setdefault: a developer
# running one file alone may ask for more.
os.environ.setdefault("OMP_NUM_THREADS", "1")

import numpy as np
import pytest


#: The files that take about 100 s or more under the driver's command, longest first, with the seconds each took
#: there (PR 45's second whole run on its builder's machine, 8 cores; CHANGES.md has the table of the last one).  A plain tuple: every xdist worker
#: must collect the same order, so nothing here is measured at run time.
LONGEST_FIRST = (
    "test_delta_kernel_compiles.py",  # ~350 since PR 50 (~400 at PR 49): two cuts' whole train steps compiled for the described chip
    "test_benchmark_mel_faults.py",   # 378
    "test_benchmark_lag_faults.py",   # 356
    "test_benchmark_q3n_faults.py",   # 311
    "test_benchmark_nmh_faults.py",   # 326 (PR 46's whole run)
    "test_carry_builder.py",          # 274
    "test_benchmark_dsv2_faults.py",  # 260
    "test_benchmark_kvl_faults.py",   # ~230 (PR 49: 14 planted faults, a process each)
    "test_multihost.py",              # 227
    "test_benchmark_nmh_correct.py",  # 246 (PR 46)
    "test_benchmark_q3n_correct.py",  # 216
    "test_benchmark_lag_correct.py",  # 204
    "test_benchmark_nmh_mixer_faults.py",  # 184 (PR 46)
    "test_examples.py",               # 196
    "test_cnn_model.py",              # 182
    "test_sparse_kernel.py",          # ~160 (PR 50: the masked core's kernels interpreted at 8,192 positions, four cases)
    "test_routed_family.py",          # 148
    "test_mellum2.py",                # 141
    "test_routed_family_steps.py",    # 130
    "test_benchmark_kvl_correct.py",  # ~130 (PR 49)
    "test_routed_family_shares.py",   # 126
    "test_lfm2_moe.py",               # 115
    "test_qwen3_next.py",             # ~110 (220 before its delta rule's tests got a file of their own)
    "test_qwen3_next_delta.py",       # ~110
    "test_routed_family_laguna.py",   # ~107 (200 with Mellum2's cases, which are now the next file)
    "test_routed_family_nemotron_h.py",  # ~105 (PR 46)
    "test_benchmark_mel_correct.py",  # 101
    "test_deepseek_v2.py",            # 99
    "test_laguna.py",                 # 98
    "test_benchmark_dsv2_correct.py", # 95
    "test_routed_family_mellum2.py",  # ~94
    "test_parallel.py",               # 88
)


def pytest_configure(config):
    """xdist hands a run's files out in the order they were collected, which ``LONGEST_FIRST`` decides; left to
    itself (3.x) it sorts them by their number of tests first, which starts a file of six two-minute tests last."""
    if hasattr(config.option, "loadscopereorder"):
        config.option.loadscopereorder = False


def pytest_collection_modifyitems(config, items):
    """The longest files are collected first, longest first.

    The driver runs ``pytest tests/ -p xdist -n 6 --dist loadfile`` under a time limit: a file is the unit a
    worker takes.  Whatever starts last runs with the other workers idle, so the run ends soonest when the longest
    file starts first (``test_multihost.py``'s real 2- and 4-process clusters are one such file; started last, as
    it was, the run ended with one worker on it and five waiting).  The order within a file, and of the files not
    named, is untouched.
    """
    rank = {name: i for i, name in enumerate(LONGEST_FIRST)}
    items.sort(key=lambda item: rank.get(item.fspath.basename, len(rank)))


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
