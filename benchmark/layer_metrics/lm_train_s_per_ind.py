"""Fenced ``train`` spans of the window (all of an individual's steps, fenced
once) per individual trained."""
import lm_spans


def read(run):
    return lm_spans.per_trained(run, "train")
