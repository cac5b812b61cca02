"""Executed conv+dense FLOPs of the window's train spans (``flops.py``:
masked-out node convs and padding slots included, they are executed) over
their fenced time over the chips' published bf16 peak."""
import flops
import spanlib


def read(run):
    found = spanlib.device_spans(run, "train")
    if not found or not run["peak"]:
        return None
    work = sum(flops.train_span_flops(run["config"], r["attrs"]["pop"], r["attrs"]["steps"])
               for r in found)
    seconds = sum(r["dur_s"] for r in found)
    return 100.0 * work / seconds / (run["peak"]["bf16_flops_per_s"] * run["chips"])
