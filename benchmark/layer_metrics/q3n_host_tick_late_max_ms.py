"""The latest wake of a ``gentun/tick`` in the traced stretch, stall or not: a paused process whose device kept its
queue shows here only (``stall_reduce.py``).  Nothing from a program without the host sampler."""
import stall_reduce


def read(run):
    got = stall_reduce.table(run)
    return None if got is None else got["host_tick_late_max_ms"]
