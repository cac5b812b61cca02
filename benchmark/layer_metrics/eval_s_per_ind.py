"""Fenced ``eval`` device spans of the window per individual trained."""
import spanlib


def read(run):
    found, n = spanlib.device_spans(run, "eval"), spanlib.trained(run)
    return sum(r["dur_s"] for r in found) / n if found and n else None
