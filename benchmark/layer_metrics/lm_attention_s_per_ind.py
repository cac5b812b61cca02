"""Self time of ``attention`` (projections, norms, rope, blockwise scores and
values) in the train and eval programs, per individual traced."""
import lm_spans


def read(run):
    return lm_spans.class_seconds_per_individual(run, ("attention",))
