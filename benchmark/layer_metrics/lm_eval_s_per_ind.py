"""Fenced ``eval`` spans of the window (the held-out batches) per individual trained."""
import lm_spans


def read(run):
    return lm_spans.per_trained(run, "eval")
