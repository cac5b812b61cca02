"""Evaluator's host time per individual trained: the wall of each
``cross_validate_population`` call minus the fenced device spans inside it
(index building, parameter init and slicing, uploads, fetches)."""
import spanlib


def read(run):
    calls = [c for u in run["units"] for c in u["calls"]]
    n = sum(c[2] for c in calls)
    if not calls or not n:
        return None
    device = [r for k in ("train", "eval", "compile") for r in spanlib.device_spans(run, k)]
    host = 0.0
    for start, wall, _ in calls:
        inside = sum(r["dur_s"] for r in device if start <= r["t_wall"] <= start + wall)
        host += wall - inside
    return host / n
