"""Self time of ``delta_core`` (the gated delta rule in chunks, ``linear_attention/core``: the l2 norms, the chunks'
decays and triangular systems, the scan over 256 chunks that carries the float32 state, and its transpose) in the
train and eval programs, per individual traced."""
import q3n_spans


def read(run):
    return q3n_spans.class_seconds_per_individual(run, ("delta_core",))
