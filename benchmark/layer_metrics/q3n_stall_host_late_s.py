"""The part of the stalls (``device_stall_s``) under ``gentun/tick`` annotations that woke ``stall_reduce.LATE_S``
or more late while the process's CPU time stood still (under half the lateness): no thread of this process ran.  A
late tick through which the process burned CPU is another thread's hold of the GIL and does not count
(``stall_reduce.py``).  Nothing from a program without the host sampler."""
import stall_reduce


def read(run):
    got = stall_reduce.table(run)
    return None if got is None else got["stall_host_late_s"]
