"""Device seconds of the eval program per individual: the durations of
``jit_lm_eval`` on the trace's "XLA Modules" line over the individuals of the
``cv_call``s traced."""
import scope_reduce
import scope_rules as rules


def read(run):
    return scope_reduce.per_individual(run, rules, rules.EVAL)
