"""Executed product FLOPs of the window's train spans (``flops.py``: recomputed
passes included, they are executed; the grouped products from the rows really
routed; each layer type's core by the block pairs its kernel visits, as the
spans carry them) over their fenced time over the chips' published bf16 peak:
the whole step's share of the peak."""
import flops
import mel_spans
from family import model_block


def read(run):
    found = mel_spans.device_spans(run, "train")
    if not found or not run["peak"]:
        return None
    config = run["config"]
    visits = {kind: mel_spans.core_visits(run, kind) for kind in flops.KERNEL_BLOCKS}
    work = flops.train_flops(model_block(config), sum(r["attrs"]["tokens"] for r in found), mel_spans.routed_rows(run),
                             config["data"]["seq_len"], visits)
    return 100.0 * work / sum(r["dur_s"] for r in found) / (run["peak"]["bf16_flops_per_s"] * run["chips"])
