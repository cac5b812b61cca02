"""Share of the family's programs' op time that no class could claim (ops
without an ``op_name``): how far to trust the per-class metrics."""
import mel_spans
import scope_rules as rules


def read(run):
    lost, busy = mel_spans.class_seconds(run, ("unattributed",)), mel_spans.class_seconds(run, rules.CLASSES)
    return 100.0 * lost / busy if busy else None
