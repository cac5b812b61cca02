"""Device memory taken on the fullest chip, in 1e9 bytes: the result line's
``memory_peak_bytes``, which is buffers plus the scratch the loaded programs
reserve (``run.MemoryPeak``), not the allocator's ``peak_bytes_in_use`` alone."""


def read(run):
    return run["memory_peak_bytes"] / 1e9 if run["memory_peak_bytes"] else None
