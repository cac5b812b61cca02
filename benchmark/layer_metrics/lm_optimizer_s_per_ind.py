"""Self time of ``optimizer`` (AdamW over every leaf and the router bias's rule)
in the train program, per individual traced."""
import lm_spans


def read(run):
    return lm_spans.class_seconds_per_individual(run, ("optimizer",))
