"""Self time of ``shared_expert`` (the shared expert's SwiGLU and its sigmoid gate, ``moe/shared``) in the train and
eval programs, per individual traced."""
import q3n_spans


def read(run):
    return q3n_spans.class_seconds_per_individual(run, ("shared_expert",))
