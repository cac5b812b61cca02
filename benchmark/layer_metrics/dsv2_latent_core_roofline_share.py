"""The causal core's share of its roofline in the train program: the FLOPs the
fused kernel's blocks execute and its least bytes (``flops.py``: forward kernel
twice and backward once a layer and step, at 192 and 128 columns, zero padding
left out) against the larger of FLOPs / peak and bytes / bandwidth
(``peaks.json``), over the self time of the core's kernels in the traced train
steps: the instructions of class ``latent_core`` that carry the kernels' name
(``splash_mqa_*``).  Where the core ran as XLA's blockwise products (no such
instruction), the whole ``latent_core`` class is the time, and the count of
FLOPs stays the kernel's."""
import dsv2_spans
import flops
import scope_reduce
import scope_rules as rules
from family import model_block


def read(run):
    trace = scope_reduce.table(run, rules)
    if not trace or not trace.get("individuals") or not run["peak"]:
        return None
    entries = [p for name, p in trace["programs"].items() if scope_reduce.base_name(name) == rules.TRAIN]
    core = [(op, t) for p in entries for op, (klass, t) in p["ops"].items() if klass == "latent_core"]
    kernels = [t for op, t in core if "splash" in op]
    seconds = sum(kernels) if kernels else sum(t for _, t in core)
    if not seconds:
        return None
    config, n = run["config"], trace["individuals"]
    m = model_block(config)
    sequences = n * config["train_steps"] * config["run"]["batch_sequences"]
    passes = (flops.CORE_FORWARD_RUNS, flops.CORE_BACKWARD_RUNS)
    work = m["num_hidden_layers"] * flops.core_flops(m, sequences, config["data"]["seq_len"], *passes)
    moved = m["num_hidden_layers"] * flops.core_bytes(m, sequences, config["data"]["seq_len"], *passes)
    by_compute, by_bandwidth = work / run["peak"]["bf16_flops_per_s"], moved / run["peak"]["hbm_bytes_per_s"]
    print(f"info dsv2_latent_core roofline: {n} individuals, {work / 1e12:.3f} TFLOP, {moved / 1e9:.2f} GB, "
          f"{seconds:.4f} s in {len(kernels)} kernel instructions ({sum(t for _, t in core):.4f} s the whole class); "
          f"bound by {'compute' if by_compute >= by_bandwidth else 'bandwidth'}")
    return 100.0 * max(by_compute, by_bandwidth) / seconds
