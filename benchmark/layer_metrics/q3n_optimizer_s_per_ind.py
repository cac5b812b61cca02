"""Self time of ``optimizer`` (AdamW over every leaf)
in the train program, per individual traced."""
import q3n_spans


def read(run):
    return q3n_spans.class_seconds_per_individual(run, ("optimizer",))
