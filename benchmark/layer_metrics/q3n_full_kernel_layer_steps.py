"""Whether the full-attention layer's core ran as the fused kernel: full layers times train steps that lowered to it,
per individual (the mean of ``attention_kernel_layer_steps_causal`` over the window's ``train`` spans: what feeds
``attention_kernel_layer_steps_total{mask="causal"}``; 8 in the cell, 0 where the program fell back to XLA's
blockwise core)."""
import q3n_spans


def read(run):
    return q3n_spans.span_mean(run, "attention_kernel_layer_steps_causal")
