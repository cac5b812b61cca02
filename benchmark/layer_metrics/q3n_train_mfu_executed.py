"""Executed product FLOPs of the window's train spans (``flops.py``: recomputed
passes included, they are executed; the grouped products from the rows really
routed; the full-attention core by the block pairs its kernel visits and the
delta rule's core by its chunk arithmetic, as the spans carry blocks and chunk)
over their fenced time over the chips' published bf16 peak: the whole step's
share of the peak."""
import flops
import q3n_spans
from family import model_block


def read(run):
    found = q3n_spans.device_spans(run, "train")
    if not found or not run["peak"]:
        return None
    config = run["config"]
    work = flops.train_flops(model_block(config), sum(r["attrs"]["tokens"] for r in found), q3n_spans.routed_rows(run),
                             config["data"]["seq_len"], q3n_spans.core_visits(run), q3n_spans.delta_chunk(run))
    return 100.0 * work / sum(r["dur_s"] for r in found) / (run["peak"]["bf16_flops_per_s"] * run["chips"])
