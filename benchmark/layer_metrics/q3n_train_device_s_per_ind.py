"""Device seconds of the train program per individual: the durations of
``jit_lm_train_step`` on the trace's "XLA Modules" line over the individuals
of the ``cv_call``s traced.  Beside ``q3n_train_s_per_ind``, the fenced span:
their difference is what fencing and launching cost."""
import scope_reduce
import scope_rules as rules


def read(run):
    return scope_reduce.per_individual(run, rules, rules.TRAIN)
