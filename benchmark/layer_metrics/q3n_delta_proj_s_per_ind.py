"""Self time of ``delta_proj`` (a linear-attention layer outside its convolution and its core: the 12,288-wide
in-projection, the [b | a] product and the gates, the per-head norm with its output gate, the out-projection) in
the train and eval programs, per individual traced."""
import q3n_spans


def read(run):
    return q3n_spans.class_seconds_per_individual(run, ("delta_proj",))
