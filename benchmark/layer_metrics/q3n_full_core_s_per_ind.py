"""Self time of ``full_core`` (the gated attention layer's causal core at a head size of 256 over 16,384 positions,
``full_attention/core``: the fused kernel forward and backward, or XLA's blockwise products) in the train and eval
programs, per individual traced."""
import q3n_spans


def read(run):
    return q3n_spans.class_seconds_per_individual(run, ("full_core",))
