"""Self time of ``expert_mm`` (the grouped products of the held experts and what
sits between them) in the train and eval programs, per individual traced."""
import mel_spans


def read(run):
    return mel_spans.class_seconds_per_individual(run, ("expert_mm",))
