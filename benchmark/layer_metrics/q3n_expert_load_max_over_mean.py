"""Routing imbalance over the held experts in the window: the busiest (routed
layer, held expert) over the mean, from the rows the window's ``fetch`` spans
report (what feeds the ``expert_rows`` counter, without set-up's share of it)."""
import q3n_spans


def read(run):
    rows = q3n_spans.rows_by_expert(run)
    return max(rows) * len(rows) / sum(rows) if rows and sum(rows) else None
