"""Self time of the convolutions (``conv_fwd``) in the eval program, per
individual traced (``scope_reduce.py``)."""
import scope_reduce
import scope_rules as rules


def read(run):
    return scope_reduce.per_individual(run, rules, rules.EVAL, ("conv_fwd",))
