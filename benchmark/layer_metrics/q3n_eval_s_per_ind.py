"""Fenced ``eval`` spans of the window (the held-out batches) per individual trained."""
import q3n_spans


def read(run):
    return q3n_spans.per_trained(run, "eval")
