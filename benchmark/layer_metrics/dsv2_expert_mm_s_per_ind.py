"""Self time of ``expert_mm`` (the grouped products of the held experts and what
sits between them) in the train and eval programs, per individual traced."""
import dsv2_spans


def read(run):
    return dsv2_spans.class_seconds_per_individual(run, ("expert_mm",))
