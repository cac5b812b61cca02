"""Executed product FLOPs of the window's train spans (``flops.py``: recomputed
passes included, they are executed; the grouped products from the rows really
routed; the core by the kernel's blocks, its zero-padded columns left out) over
their fenced time over the chips' published bf16 peak."""
import dsv2_spans
import flops
from family import model_block


def read(run):
    found = dsv2_spans.device_spans(run, "train")
    if not found or not run["peak"]:
        return None
    config = run["config"]
    work = flops.train_flops(model_block(config), sum(r["attrs"]["tokens"] for r in found), dsv2_spans.routed_rows(run),
                             config["data"]["seq_len"])
    return 100.0 * work / sum(r["dur_s"] for r in found) / (run["peak"]["bf16_flops_per_s"] * run["chips"])
