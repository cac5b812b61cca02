"""Fenced ``init_params`` spans of the window (``lm_init``: a fresh train state
from the genome's content hash) per individual trained."""
import q3n_spans


def read(run):
    return q3n_spans.per_trained(run, "init_params")
