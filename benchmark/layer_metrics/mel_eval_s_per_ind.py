"""Fenced ``eval`` spans of the window (the held-out batches) per individual trained."""
import mel_spans


def read(run):
    return mel_spans.per_trained(run, "eval")
