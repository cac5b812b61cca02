"""Share of the train and eval programs' op time that no class could claim
(ops without an ``op_name``): how far to trust the per-class metrics."""
import scope_reduce
import scope_rules as rules


def read(run):
    trace = scope_reduce.table(run, rules)
    if not trace:
        return None
    both = [scope_reduce.merged(trace, p)["classes"] for p in rules.PROGRAMS]
    busy = sum(sum(c.values()) for c in both)
    return 100.0 * sum(c.get(scope_reduce.UNATTRIBUTED, 0.0) for c in both) / busy if busy else None
