"""Self time of ``moe_route`` (router, top-k, sort, gather of rows, un-sort and
weighted sum) in the train and eval programs, per individual traced."""
import mel_spans


def read(run):
    return mel_spans.class_seconds_per_individual(run, ("moe_route",))
