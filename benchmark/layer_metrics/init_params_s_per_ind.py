"""Host time of the window's ``init_params`` and ``fold_slice`` spans per
individual trained: launching the jitted parameter init, and per fold the
eager slicing of every leaf plus ``init_pop``."""
import spanlib


def read(run):
    found = spanlib.spans(run, "init_params") + spanlib.spans(run, "fold_slice")
    n = spanlib.trained(run)
    return sum(r["dur_s"] for r in found) / n if found and n else None
