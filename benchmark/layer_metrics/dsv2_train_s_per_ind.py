"""Fenced ``train`` spans of the window (all of an individual's steps, fenced
once) per individual trained."""
import dsv2_spans


def read(run):
    return dsv2_spans.per_trained(run, "train")
