"""The part of the stalls (``device_stall_s``) with no program run open on "XLA Modules": the next program came
late; the rest lies inside a run (``stall_reduce.py``)."""
import stall_reduce


def read(run):
    got = stall_reduce.table(run)
    return None if got is None else got["stall_between_programs_s"]
