"""Set-up spent in population attempts that ended in the out-of-memory error
the healer cures (``oom_attempt`` spans before the window): paid by every
process of the deep configuration, whose learned cap lives in process memory."""
import spanlib


def read(run):
    found = spanlib.spans(run, "oom_attempt", "setup")
    return sum(r["dur_s"] for r in found) if found else None
