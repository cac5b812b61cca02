"""Self time of ``shared_expert`` (the shared experts' SwiGLU, ``moe/shared``) in
the train and eval programs, per individual traced."""
import dsv2_spans


def read(run):
    return dsv2_spans.class_seconds_per_individual(run, ("shared_expert",))
