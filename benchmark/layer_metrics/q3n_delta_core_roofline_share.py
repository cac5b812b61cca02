"""The delta rule's core's share of its roofline in the train program (``q3n_spans.delta_core_roofline_share``): the
executed chunk arithmetic and the least bytes of its operands, chunk states and outputs (``flops.py``) against the
larger of FLOPs / peak and bytes / bandwidth, over the self time of everything under ``linear_attention/core``."""
import q3n_spans


def read(run):
    return q3n_spans.delta_core_roofline_share(run)
