"""Fenced ``eval`` spans of the window (the held-out batches) per individual trained."""
import dsv2_spans


def read(run):
    return dsv2_spans.per_trained(run, "eval")
