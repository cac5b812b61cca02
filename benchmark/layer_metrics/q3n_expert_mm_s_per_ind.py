"""Self time of ``expert_mm`` (the grouped products of the held experts and what
sits between them) in the train and eval programs, per individual traced."""
import q3n_spans


def read(run):
    return q3n_spans.class_seconds_per_individual(run, ("expert_mm",))
