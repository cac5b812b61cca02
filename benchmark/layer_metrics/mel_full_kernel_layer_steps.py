"""Whether the full-attention layers' core ran as the fused kernel: full layers
times train steps that lowered to it, per individual (the mean of
``attention_kernel_layer_steps_causal`` over the window's ``train`` spans: what
feeds ``attention_kernel_layer_steps_total{mask="causal"}``; 16 in the cell, 0
where the program fell back to XLA's blockwise core)."""
import mel_spans


def read(run):
    return mel_spans.kernel_layer_steps(run, "causal")
