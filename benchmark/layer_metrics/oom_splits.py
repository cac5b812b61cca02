"""Populations the OOM healer had to split inside the window (``oom_split``
events); those of set-up are printed on an earlier line."""
import spanlib


def read(run):
    print(f"info oom_splits in set-up: {len(spanlib.events(run, 'oom_split', 'setup'))}")
    return float(len(spanlib.events(run, "oom_split")))
