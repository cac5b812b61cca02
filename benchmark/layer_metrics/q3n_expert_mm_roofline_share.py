"""The grouped products' share of their roofline in the train program, at a
contraction of 2048 and the expert width of 512 (32 groups of ~320 rows): executed FLOPs and least bytes
(``flops.py``, from the rows the traced individuals really routed, 4 passes a
step) against the larger of FLOPs / peak and bytes / bandwidth (``peaks.json``),
over the traced ``expert_mm`` self time."""
import flops
import q3n_spans
import scope_rules as rules
from family import model_block


def read(run):
    seconds = q3n_spans.class_seconds(run, ("expert_mm",), (rules.TRAIN,))
    if not seconds or not run["peak"]:
        return None
    n = run["scope_table"]["individuals"]
    m, rows = model_block(run["config"]), q3n_spans.routed_rows(run, n)
    if not rows:
        return None
    layer_calls = n * run["config"]["train_steps"] * m["num_hidden_layers"]
    work = flops.expert_mm_flops(m, rows, flops.TRAIN_PASSES)
    moved = flops.expert_mm_bytes(m, rows, flops.TRAIN_PASSES, layer_calls)
    by_compute, by_bandwidth = work / run["peak"]["bf16_flops_per_s"], moved / run["peak"]["hbm_bytes_per_s"]
    print(f"info q3n_expert_mm roofline: {rows:.0f} rows of {n} individuals, {work / 1e12:.3f} TFLOP, "
          f"{moved / 1e9:.2f} GB, {seconds:.4f} s traced; bound by "
          f"{'compute' if by_compute >= by_bandwidth else 'bandwidth'}")
    return 100.0 * max(by_compute, by_bandwidth) / seconds
