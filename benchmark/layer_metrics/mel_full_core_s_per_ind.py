"""Self time of ``full_core`` (the full-attention layers' core: the fused
kernel's calls under the causal mask, or the blockwise core's products, and
what sits around them) in the train and eval programs, per individual traced."""
import mel_spans


def read(run):
    return mel_spans.class_seconds_per_individual(run, ("full_core",))
