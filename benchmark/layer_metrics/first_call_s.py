"""Set-up spent in first calls of model programs: the ``compile`` spans
(compile or cache load, plus the first execution) before the window."""
import spanlib


def read(run):
    found = spanlib.device_spans(run, "compile", "setup")
    m = run["monitor"]
    lo = run["window"][0]
    print(f"info first calls in set-up: {len(found)}; jax asked for "
          f"{sum(t < lo for t in m.requests)} programs, {sum(t < lo for t in m.hits)} cache hits")
    return sum(r["dur_s"] for r in found) if found else None
