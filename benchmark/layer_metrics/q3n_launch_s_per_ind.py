"""Host time the window's jitted calls took to return (``dispatch_s`` of the
fenced ``train`` span, all of an individual's steps, and of its ``eval``
span) per individual trained: launch, apart from the wait that follows."""
import q3n_spans
import spanlib


def read(run):
    found = [r for kind in ("train", "eval") for r in q3n_spans.device_spans(run, kind)
             if "dispatch_s" in r["attrs"]]
    n = spanlib.trained(run)
    return sum(r["attrs"]["dispatch_s"] for r in found) / n if found and n else None
