"""Seconds of the traced stretch in stalls: single gaps of ``stall_reduce.STALL_S`` or more with no op on the
device, mean over the chips (``stall_reduce.py``).  0.0 in a run without one."""
import stall_reduce


def read(run):
    got = stall_reduce.table(run)
    return None if got is None else got["device_stall_s"]
