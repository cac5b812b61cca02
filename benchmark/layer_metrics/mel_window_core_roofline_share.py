"""The sliding-window layers' core's share of its roofline in the train program
(``mel_spans.core_roofline_share``): the FLOPs of the block pairs the kernel
visits under the window's mask -- its own count, off the ``train`` span -- and
its least bytes against the larger of FLOPs / peak and bytes / bandwidth, over
the self time of the ``splash_mqa_*`` instructions under ``sliding_attention/core``."""
import mel_spans


def read(run):
    return mel_spans.core_roofline_share(run, "sliding_attention")
