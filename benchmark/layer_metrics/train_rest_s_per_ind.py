"""Self time of ``head`` + ``rest`` (loss, optimizer, batch gather, rng, loop
bookkeeping) in the train program, per individual traced (``scope_reduce.py``)."""
import scope_reduce
import scope_rules as rules


def read(run):
    return scope_reduce.per_individual(run, rules, rules.TRAIN, ("head", "rest"))
