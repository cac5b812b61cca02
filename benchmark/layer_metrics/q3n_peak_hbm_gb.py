"""Device memory taken on the chip, in 1e9 bytes: the result line's
``memory_peak_bytes`` (buffers plus the scratch the loaded programs reserve)."""


def read(run):
    return run["memory_peak_bytes"] / 1e9 if run["memory_peak_bytes"] else None
