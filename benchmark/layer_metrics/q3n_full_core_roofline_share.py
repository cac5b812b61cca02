"""The full-attention core's share of its roofline in the train program (``q3n_spans.full_core_roofline_share``): the
FLOPs of the block pairs the kernel visits under the causal mask -- its own count, off the ``train`` span -- and its
least bytes against the larger of FLOPs / peak and bytes / bandwidth, over the self time of the ``splash_mqa_*``
instructions under ``full_attention/core``."""
import q3n_spans


def read(run):
    return q3n_spans.full_core_roofline_share(run)
