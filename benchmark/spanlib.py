"""Small helpers the per-layer metric readers share: picking spans of a run.

``run`` is what ``run.py`` hands every reader: ``records`` (the program's span
and event records), ``units`` (one per unit of work, with ``calls``: the
``cross_validate_population`` calls it made as (wall start, seconds,
individuals trained)), ``window`` (wall start, wall end), ``monitor`` (jax's
own compile events), ``trace`` (``trace_reduce.reduce``'s result or None),
``memory_peak_bytes``, ``config``, ``cell``, ``chips`` and ``peak``.
"""

from __future__ import annotations

from typing import Any, Dict, List


def _when(run: Dict[str, Any], where: str):
    """Predicate on a wall time: inside the window, or (``setup``) before it."""
    lo, hi = run["window"]
    return (lambda t: lo <= t <= hi) if where == "window" else (lambda t: t < lo)


def spans(run: Dict[str, Any], kind: str, where: str = "window") -> List[Dict[str, Any]]:
    """Span records of ``kind`` that started in the window (or before it)."""
    keep = _when(run, where)
    return [r for r in run["records"]
            if r.get("type") == "span" and r["kind"] == kind and keep(r["t_wall"])]


def device_spans(run: Dict[str, Any], kind: str, where: str = "window") -> List[Dict[str, Any]]:
    """The model's device spans (``models/cnn.py``): those that carry a fold.
    ``kind`` is ``train``, ``eval`` or ``compile`` (a program's first call)."""
    return [r for r in spans(run, kind, where) if "fold" in (r.get("attrs") or {})]


def events(run: Dict[str, Any], name: str, where: str = "window") -> List[Dict[str, Any]]:
    keep = _when(run, where)
    return [r for r in run["records"]
            if r.get("type") == "event" and r.get("name") == name and keep(r["t_wall"])]


def trained(run: Dict[str, Any]) -> int:
    return sum(u["trained"] for u in run["units"])
