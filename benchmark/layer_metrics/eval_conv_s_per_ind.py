"""Self time of the convolutions (``conv_fwd``) in the eval program, per
individual traced (``scope_reduce.py``)."""
import scope_reduce


def read(run):
    return scope_reduce.per_individual(run, scope_reduce.EVAL, ("conv_fwd",))
