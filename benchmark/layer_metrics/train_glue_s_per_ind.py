"""Self time of ``glue`` in the train program, per individual traced: mask
sums, node gating, stage merge, pooling, relu and casts around the
convolutions (``scope_reduce.py``); what ROADMAP S2 (b)-(d) may remove."""
import scope_reduce
import scope_rules as rules


def read(run):
    return scope_reduce.per_individual(run, rules, rules.TRAIN, ("glue",))
