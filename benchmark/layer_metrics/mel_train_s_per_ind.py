"""Fenced ``train`` spans of the window (all of an individual's steps, fenced
once) per individual trained."""
import mel_spans


def read(run):
    return mel_spans.per_trained(run, "train")
