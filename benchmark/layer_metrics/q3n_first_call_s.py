"""Set-up spent in first calls of the family's programs: the ``compile`` spans
(compile or cache load, plus the first execution) before the window."""
import q3n_spans


def read(run):
    found = q3n_spans.device_spans(run, "compile", "setup")
    return sum(r["dur_s"] for r in found) if found else None
