"""Self time of ``optimizer`` (AdamW over every leaf)
in the train program, per individual traced."""
import mel_spans


def read(run):
    return mel_spans.class_seconds_per_individual(run, ("optimizer",))
