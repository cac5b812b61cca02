"""Tracing/profiling hooks (SURVEY.md §5 "Tracing / profiling": absent in
the reference; the rebuild adds cheap, high-value instrumentation).

Two tools:

- :func:`trace` — context manager around ``jax.profiler`` producing a
  TensorBoard-loadable trace of the fitness hot path;
- :class:`EvalTimer` — per-evaluation wall/throughput record keeping, the
  source of the north-star metric (individuals/hour/chip) at finer grain
  than the per-generation log.

:class:`EvalTimer` keeps its own records and emits nothing into the telemetry
plane (``gentun_tpu/telemetry``, docs/OBSERVABILITY.md); code that wants a
span opens one directly (``telemetry.span(...)``).
"""

from __future__ import annotations

import contextlib
import json
import logging
import time
from typing import Any, Dict, List, Optional

__all__ = ["trace", "EvalTimer"]

logger = logging.getLogger("gentun_tpu")


@contextlib.contextmanager
def trace(logdir: str, enabled: bool = True):
    """``with trace('/tmp/tb'): population.evaluate()`` → profiler dump.

    No-ops cleanly when disabled or when jax is unavailable, so call sites
    can leave the hook in place unconditionally.
    """
    if not enabled:
        yield
        return
    try:
        import jax.profiler as jprof
    except ImportError:  # pragma: no cover
        yield
        return
    jprof.start_trace(logdir)
    try:
        yield
    finally:
        jprof.stop_trace()
        logger.info("profiler trace written to %s", logdir)


class EvalTimer:
    """Accumulates per-evaluation timings; reports the north-star metric."""

    def __init__(self, n_chips: int = 1):
        self.n_chips = max(1, int(n_chips))
        self.records: List[Dict[str, Any]] = []

    @contextlib.contextmanager
    def measure(self, n_individuals: int, label: str = ""):
        t0 = time.monotonic()
        yield
        elapsed = max(time.monotonic() - t0, 1e-9)
        rec = {
            "label": label,
            "individuals": int(n_individuals),
            "wall_s": round(elapsed, 4),
            "individuals_per_hour_per_chip": round(
                n_individuals / (elapsed / 3600.0) / self.n_chips, 2
            ),
        }
        self.records.append(rec)
        logger.info("eval %s", json.dumps(rec))

    @property
    def total_individuals(self) -> int:
        return sum(r["individuals"] for r in self.records)

    def summary(self) -> Dict[str, Any]:
        wall = max(sum(r["wall_s"] for r in self.records), 1e-9)
        n = self.total_individuals
        return {
            "individuals": n,
            "wall_s": round(wall, 3),
            "individuals_per_hour_per_chip": round(n / (wall / 3600.0) / self.n_chips, 2),
        }
