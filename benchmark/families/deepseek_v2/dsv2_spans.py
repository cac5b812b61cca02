"""What the ``dsv2_*`` readers under ``layer_metrics/`` share: the model's fenced
device spans (those that carry ``scope_rules.SPAN_ATTR``), the rows routed to
the held experts as the ``fetch`` spans report them, and op-class seconds
summed over the family's programs."""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence

import scope_reduce
import scope_rules as rules
import spanlib

DEVICE_KINDS = ("train", "eval", "init_params", "compile")


def device_spans(run: Dict[str, Any], kind: str, where: str = "window") -> List[Dict[str, Any]]:
    return [r for r in spanlib.spans(run, kind, where) if rules.SPAN_ATTR in (r.get("attrs") or {})]


def per_trained(run: Dict[str, Any], kind: str) -> Optional[float]:
    """Fenced spans of ``kind`` in the window per individual trained."""
    found, n = device_spans(run, kind), spanlib.trained(run)
    return sum(r["dur_s"] for r in found) / n if found and n else None


def routed_rows(run: Dict[str, Any], first: Optional[int] = None) -> float:
    """Rows routed to the held experts by the window's individuals (the first
    ``first`` of them), all routed layers and train steps together."""
    found = sorted(device_spans(run, "fetch"), key=lambda r: r["t_wall"])[:first]
    return float(sum(sum(map(sum, r["attrs"].get("expert_rows", []))) for r in found))


def rows_by_expert(run: Dict[str, Any]) -> List[float]:
    """Rows of each (routed layer, held expert), the window's individuals together."""
    per_individual = [r["attrs"]["expert_rows"] for r in device_spans(run, "fetch") if r["attrs"].get("expert_rows")]
    return [float(sum(cell)) for layer in zip(*per_individual) for cell in zip(*layer)]


def class_seconds(run: Dict[str, Any], classes: Sequence[str], programs: Sequence[str] = rules.PROGRAMS
                  ) -> Optional[float]:
    """Self seconds of ``classes`` over ``programs`` in the traced stretch (all individuals traced)."""
    trace = scope_reduce.table(run, rules)
    if not trace or not trace.get("individuals"):
        return None
    entries = [scope_reduce.merged(trace, p) for p in programs]
    if not any(e["runs"] for e in entries):
        return None
    return sum(e["classes"].get(c, 0.0) for e in entries for c in classes)


def class_seconds_per_individual(run: Dict[str, Any], classes: Sequence[str]) -> Optional[float]:
    seconds = class_seconds(run, classes)
    return None if seconds is None else seconds / run["scope_table"]["individuals"]
