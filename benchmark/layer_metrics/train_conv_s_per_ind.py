"""Self time of the convolutions (``conv_fwd`` + ``conv_bwd``, the fusions they
sit in) in the train program, per individual traced (``scope_reduce.py``)."""
import scope_reduce
import scope_rules as rules


def read(run):
    return scope_reduce.per_individual(run, rules, rules.TRAIN, ("conv_fwd", "conv_bwd"))
