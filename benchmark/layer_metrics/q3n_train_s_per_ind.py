"""Fenced ``train`` spans of the window (all of an individual's steps, fenced
once) per individual trained."""
import q3n_spans


def read(run):
    return q3n_spans.per_trained(run, "train")
