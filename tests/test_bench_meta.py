"""Bench bookkeeping tests (no TPU, no model runs).

The measurement itself runs on the real chip (driver-invoked); this pins
the pure logic around it: the analytic FLOPs model's inputs.  That the
bench refuses a CPU and an unknown device kind is in
``tests/test_chip_smoke.py``.
"""

import sys

import pytest

sys.path.insert(0, __import__("os").path.dirname(__import__("os").path.dirname(__import__("os").path.abspath(__file__))))

import bench  # noqa: E402


def test_flops_model_matches_schedule_shape():
    """schedule_flops scales linearly in pop and epochs (sanity pins)."""
    f1 = bench.schedule_flops(bench.PROXY, pop=10)
    f2 = bench.schedule_flops(bench.PROXY, pop=20)
    assert f2 == pytest.approx(2 * f1)
    # doubling epochs doubles the train term but not the eval term
    more_epochs = dict(bench.PROXY, epochs=(2,))
    assert 1.4 * f1 < bench.schedule_flops(more_epochs, 10) < 2 * f1
