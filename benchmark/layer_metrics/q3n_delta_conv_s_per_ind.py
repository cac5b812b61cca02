"""Self time of ``delta_conv`` (the causal depthwise convolution of four taps over the 8,192 q, k and v columns and
its SiLU, ``linear_attention/conv``) in the train and eval programs, per individual traced."""
import q3n_spans


def read(run):
    return q3n_spans.class_seconds_per_individual(run, ("delta_conv",))
