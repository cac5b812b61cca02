"""Evaluator's host time per individual trained: the wall of each
``cross_validate_population`` call minus the fenced device spans inside it
(configuration, hashes, batch plan, uploads, the fetch of losses)."""
import q3n_spans


def read(run):
    calls = [c for u in run["units"] for c in u["calls"]]
    n = sum(c[2] for c in calls)
    device = [r for k in q3n_spans.DEVICE_KINDS for r in q3n_spans.device_spans(run, k)]
    if not calls or not n or not device:
        return None
    host = sum(wall - sum(r["dur_s"] for r in device if start <= r["t_wall"] <= start + wall)
               for start, wall, _ in calls)
    return host / n
