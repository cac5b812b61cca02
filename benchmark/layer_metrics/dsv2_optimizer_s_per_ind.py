"""Self time of ``optimizer`` (AdamW over every leaf and the router bias's rule)
in the train program, per individual traced."""
import dsv2_spans


def read(run):
    return dsv2_spans.class_seconds_per_individual(run, ("optimizer",))
