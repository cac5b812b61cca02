"""Executed product FLOPs of the window's train spans (``flops.py``: recomputed
passes included, they are executed; the grouped products from the rows really
routed) over their fenced time over the chips' published bf16 peak."""
import flops
import lm_spans
from family import model_block


def read(run):
    found = lm_spans.device_spans(run, "train")
    if not found or not run["peak"]:
        return None
    config = run["config"]
    work = flops.train_flops(model_block(config), sum(r["attrs"]["tokens"] for r in found), lm_spans.routed_rows(run),
                             config["data"]["seq_len"], config["run"]["attn_block"])
    return 100.0 * work / sum(r["dur_s"] for r in found) / (run["peak"]["bf16_flops_per_s"] * run["chips"])
