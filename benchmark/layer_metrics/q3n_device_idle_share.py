"""Share of the traced stretch of the window in which no operation ran on the
device (``trace_reduce.py``)."""


def read(run):
    trace = run["trace"]
    return 100.0 * trace["idle_share"] if trace and trace["busy_s"] > 0 else None
