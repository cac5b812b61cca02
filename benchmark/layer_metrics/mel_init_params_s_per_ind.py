"""Fenced ``init_params`` spans of the window (``lm_init``: a fresh train state
from the genome's content hash) per individual trained."""
import mel_spans


def read(run):
    return mel_spans.per_trained(run, "init_params")
