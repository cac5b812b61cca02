"""Share of the family's programs' op time that no class could claim (ops
without an ``op_name``): how far to trust the per-class metrics."""
import q3n_spans
import scope_rules as rules


def read(run):
    lost, busy = q3n_spans.class_seconds(run, ("unattributed",)), q3n_spans.class_seconds(run, rules.CLASSES)
    return 100.0 * lost / busy if busy else None
