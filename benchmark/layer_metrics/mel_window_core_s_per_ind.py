"""Self time of ``window_core`` (the sliding-window layers' core: the fused
kernel's calls under the window's mask, or the blockwise core's products over
the window's keys, and what sits around them) in the train and eval programs,
per individual traced."""
import mel_spans


def read(run):
    return mel_spans.class_seconds_per_individual(run, ("window_core",))
