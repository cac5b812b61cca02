"""Self time of ``moe_route`` (router, top-k, sort, gather of rows, un-sort and
weighted sum) in the train and eval programs, per individual traced."""
import q3n_spans


def read(run):
    return q3n_spans.class_seconds_per_individual(run, ("moe_route",))
